"""File-spool request/response transport for the serving engine.

Reference analog: the reference exposes workloads through cluster
Services; this environment has no network, so the serving job's request
surface is a spool DIRECTORY (the same local-IPC substrate the
supervisor's store/progress layers ride). The protocol is the classic
maildir trick: writers create a temp file and ``rename`` it into place
— rename is atomic on POSIX, so the scanner never sees a torn file —
and the engine claims a request by renaming it out of ``requests/``,
so an in-flight request is never double-served. A crashed engine
leaves its claims in ``claimed/``; the serve workload calls
:meth:`Spool.recover_claimed` at startup to move them back into
``requests/`` (the supervisor's restart policy re-runs the job, and
the orphaned clients would otherwise wait out their timeouts).

Layout under the spool root:

    requests/<id>.json     submitted, unclaimed (one record)
    requests/b-<id>.jsonb  submitted, unclaimed (a BATCH of records)
    claimed/...            claimed by the engine (in flight)
    responses/<id>.json    completed (tokens + latency record)

Batched framing (the serve plane's syscall collapse): a ``.jsonb``
file carries MANY requests — one crc-guarded frame per line — written
with ONE temp file, ONE fsync, and ONE rename, and claimed with ONE
rename, so the per-request syscall count drops by the batch factor.
The frame format is torn-tolerant by construction: every complete
frame ends in a newline and carries its own crc32, so a reader of a
file some foreign writer tore mid-write (no tmp+rename discipline)
recovers every complete record and drops only the torn tail —
:func:`decode_frames` is the single decoder both sides use.
"""

from __future__ import annotations

import json
import os
import time
import uuid
import zlib
from collections import deque
from pathlib import Path
from typing import Dict, List, Optional, Set, Tuple

from ..backoff import Backoff
from ..obs.trace import serve_span, tracer as _span_tracer

# Batch files: many frames per spool file. ``.recovered.jsonb`` marks a
# batch a crashed engine left in claimed/ and recover_claimed() moved
# back — ONLY those pay the per-record response-dedup check on
# re-claim (a record of the batch may have been answered before the
# crash; re-serving it would waste capacity and, without respond_once
# at the publication point, risk a duplicate).
BATCH_SUFFIX = ".jsonb"
RECOVERED_MARK = ".recovered"

# Adaptive response-wait schedule: a client polling for a response
# that is still cooking backs off exponentially instead of burning a
# fixed-interval stat() loop (the shared backoff.py schedule — same
# discipline as rendezvous joins and checkpoint retries).
WAIT_BACKOFF = Backoff(base_s=0.002, cap_s=0.25, factor=1.7, jitter=0.1)


def encode_frames(recs: List[dict]) -> bytes:
    """Frame records for a batch file: one line per record,
    ``<crc32 of payload, 8 hex>:<payload json>\\n``. The crc covers the
    payload bytes, so a torn or bit-flipped line is detected without
    trusting json to fail."""
    out = []
    for rec in recs:
        payload = json.dumps(rec, separators=(",", ":")).encode()
        out.append(b"%08x:" % (zlib.crc32(payload) & 0xFFFFFFFF))
        out.append(payload)
        out.append(b"\n")
    return b"".join(out)


def decode_frames(data: bytes) -> Tuple[List[dict], int]:
    """Decode a batch file's frames; returns ``(records, torn)``.

    Torn-tolerant: a line without a trailing newline (the classic
    crash-mid-write shape), a crc mismatch, or unparseable json counts
    as torn and is SKIPPED — every complete frame before, between and
    after torn ones is recovered."""
    recs: List[dict] = []
    torn = 0
    end = len(data)
    pos = 0
    while pos < end:
        nl = data.find(b"\n", pos)
        if nl < 0:
            torn += 1  # torn tail: the writer died mid-line
            break
        line = data[pos:nl]
        pos = nl + 1
        if not line:
            continue
        if len(line) < 10 or line[8:9] != b":":
            torn += 1
            continue
        payload = line[9:]
        try:
            crc = int(line[:8], 16)
        except ValueError:
            torn += 1
            continue
        if (zlib.crc32(payload) & 0xFFFFFFFF) != crc:
            torn += 1
            continue
        try:
            rec = json.loads(payload)
        except json.JSONDecodeError:
            torn += 1
            continue
        if isinstance(rec, dict):
            recs.append(rec)
        else:
            torn += 1
    return recs, torn


def make_request(
    *,
    prompt=None,
    prompt_len: Optional[int] = None,
    max_new_tokens: int = 64,
    request_id: Optional[str] = None,
) -> dict:
    """Build a request record (the :meth:`Spool.submit` payload shape).

    ``prompt`` is an explicit token-id list; ``prompt_len`` asks the
    engine to synthesize a deterministic prompt of that length (no
    tokenizer ships in this environment). Exactly one must be set.

    Every request carries a trace context frame field ``tctx`` —
    ``{"o": origin wall ts, "p": parent span id}`` — threaded verbatim
    through every hop (front spool → router lane → ring/spill →
    engine) so each process can emit its hop span against the SAME
    request identity. The parent span id is derived from the rid
    (crc32, 8 hex) rather than drawn fresh: a replayed record after a
    torn-batch recovery re-derives the identical id, so replay cannot
    fork a request's waterfall. With tracing disabled the field is a
    few bytes of dead weight per frame and nothing reads it."""
    if (prompt is None) == (prompt_len is None):
        raise ValueError("exactly one of prompt / prompt_len required")
    rid = request_id or uuid.uuid4().hex[:12]
    submit = time.time()
    return {
        "id": rid,
        "prompt": list(map(int, prompt)) if prompt is not None else None,
        "prompt_len": prompt_len,
        "max_new_tokens": int(max_new_tokens),
        "submit_time": submit,
        "tctx": {
            "o": round(submit, 6),
            "p": "%08x" % (zlib.crc32(rid.encode()) & 0xFFFFFFFF),
        },
    }


class SpoolIOCounters:
    """Per-spool op accounting — the serve plane's syscall budget is
    pinned against these (batched framing must collapse ops/request),
    and the adaptive wait schedule is pinned by ``polls``."""

    __slots__ = (
        "creates", "renames", "links", "unlinks", "scans", "reads",
        "fsyncs", "polls",
    )

    def __init__(self) -> None:
        for k in self.__slots__:
            setattr(self, k, 0)

    def snapshot(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}

    def total(self) -> int:
        return sum(getattr(self, k) for k in self.__slots__)


class Spool:
    def __init__(self, root: Path | str, create: bool = True):
        self.root = Path(root)
        self.requests = self.root / "requests"
        self.claimed = self.root / "claimed"
        self.responses = self.root / "responses"
        self.io = SpoolIOCounters()
        # Batch-claim bookkeeping: records claimed but not yet returned
        # (a batch bigger than the claim limit), and per-batch-file
        # outstanding rid sets (the claimed ``.jsonb`` is unlinked when
        # its last record is responded).
        self._carry: deque = deque()
        self._batch_pending: Dict[Path, Set[str]] = {}
        self._rid_batch: Dict[str, Path] = {}
        if create:
            for d in (self.requests, self.claimed, self.responses):
                d.mkdir(parents=True, exist_ok=True)

    # ---- client side ----

    def submit(
        self,
        *,
        prompt=None,
        prompt_len: Optional[int] = None,
        max_new_tokens: int = 64,
        request_id: Optional[str] = None,
    ) -> str:
        """Drop a request into the spool; returns its id."""
        rec = make_request(
            prompt=prompt,
            prompt_len=prompt_len,
            max_new_tokens=max_new_tokens,
            request_id=request_id,
        )
        return self.enqueue(rec)

    def enqueue(self, rec: dict) -> str:
        """Drop a fully-formed request record into ``requests/`` (the
        single-record primitive: unlike :meth:`submit` it preserves
        the record verbatim — id, prompt, and above all the client's
        original ``submit_time``, which the engine's TTFT accounting is
        measured from)."""
        rid = rec["id"]
        t0 = time.time()
        tmp = self.requests / f".{rid}.tmp"
        tmp.write_text(json.dumps(rec))
        self.io.creates += 1
        os.rename(tmp, self.requests / f"{rid}.json")
        self.io.renames += 1
        # Client-enqueue hop span. Dispatch copies the router spills to
        # a REPLICA spool carry "attempts" — those get a dispatch span
        # at the router instead, never a second enqueue.
        if _span_tracer() is not None and "tctx" in rec and "attempts" not in rec:
            serve_span("enqueue", t0, time.time() - t0, rid=rid)
        return rid

    def enqueue_batch(self, recs: List[dict], fsync: bool = True) -> List[str]:
        """Drop MANY request records as ONE spool file: one temp write,
        one (optional) fsync, one rename — the per-request syscall
        count collapses by the batch factor. Returns the rids in frame
        order. An empty batch writes nothing."""
        if not recs:
            return []
        rids = [rec["id"] for rec in recs]
        t0 = time.time()
        bid = uuid.uuid4().hex[:12]
        tmp = self.requests / f".b-{bid}.tmp"
        with open(tmp, "wb") as fh:
            fh.write(encode_frames(recs))
            if fsync:
                fh.flush()
                os.fsync(fh.fileno())
                self.io.fsyncs += 1
        self.io.creates += 1
        os.rename(tmp, self.requests / f"b-{bid}{BATCH_SUFFIX}")
        self.io.renames += 1
        if _span_tracer() is not None:
            dur = time.time() - t0
            for rec in recs:
                if "tctx" in rec and "attempts" not in rec:
                    serve_span("enqueue", t0, dur, rid=rec["id"], batch=len(recs))
        return rids

    def wait_response(self, request_id: str, timeout: float = 60.0) -> dict:
        """Poll for the response record; raises TimeoutError.

        The poll interval follows the shared adaptive backoff schedule
        (2 ms first check, exponential to a 250 ms cap) — an idle
        client waiting out a slow decode costs tens of stat()s, not
        ``timeout / fixed_interval`` of them."""
        path = self.responses / f"{request_id}.json"
        # monotonic: the poll budget is a within-process interval; a
        # clock step must not time out a request that is still cooking.
        deadline = time.monotonic() + timeout
        attempt = 0
        while time.monotonic() < deadline:
            self.io.polls += 1
            if path.exists():
                return json.loads(path.read_text())
            delay = WAIT_BACKOFF.delay(attempt)
            attempt += 1
            time.sleep(min(delay, max(0.0, deadline - time.monotonic())))
        raise TimeoutError(f"no response for {request_id} in {timeout}s")

    # ---- engine side ----

    def _claim_batch_file(self, path: Path, out: List[dict]) -> None:
        """Claim one ``.jsonb`` batch: rename whole-file (exactly-once
        vs concurrent claimers), decode every complete frame, register
        the per-record claim bookkeeping. Records of a RECOVERED batch
        that already have a response are dropped (served before the
        previous life crashed)."""
        dst = self.claimed / path.name
        try:
            os.rename(path, dst)
        except FileNotFoundError:
            return  # lost the race with another claimer
        self.io.renames += 1
        try:
            data = dst.read_bytes()
        except OSError:
            return
        self.io.reads += 1
        recs, _torn = decode_frames(data)
        recovered = RECOVERED_MARK in path.name
        pending: Set[str] = set()
        for rec in recs:
            rid = rec.get("id")
            if not rid:
                continue
            if recovered and self.has_response(rid):
                continue
            pending.add(rid)
            self._rid_batch[rid] = dst
            out.append(rec)
        if pending:
            self._batch_pending[dst] = pending
        else:
            dst.unlink(missing_ok=True)
            self.io.unlinks += 1

    def claim(self, limit: int) -> list[dict]:
        """Claim up to ``limit`` unclaimed requests, oldest first.
        Batch files are claimed whole (one rename); records beyond the
        limit are carried in memory and returned by the next call —
        their durable copy stays in ``claimed/`` until responded."""
        out: list[dict] = []
        limit = max(0, limit)
        while self._carry and len(out) < limit:
            out.append(self._carry.popleft())
        if len(out) >= limit:
            return out

        def mtime(p):
            # A concurrent claimer may rename the file between iterdir
            # and stat; such entries sort last and lose the per-file
            # rename race below instead of aborting the whole batch.
            try:
                return p.stat().st_mtime
            except FileNotFoundError:
                return float("inf")

        try:
            self.io.scans += 1
            pending = sorted(
                (
                    p
                    for p in self.requests.iterdir()
                    if p.suffix in (".json", BATCH_SUFFIX)
                ),
                key=mtime,
            )
        except FileNotFoundError:
            return out
        for path in pending:
            if len(out) >= limit:
                break
            if path.suffix == BATCH_SUFFIX:
                batch: List[dict] = []
                self._claim_batch_file(path, batch)
                for rec in batch:
                    if len(out) < limit:
                        out.append(rec)
                    else:
                        self._carry.append(rec)
                continue
            dst = self.claimed / path.name
            try:
                os.rename(path, dst)
            except FileNotFoundError:
                continue  # lost a race with another claimer
            self.io.renames += 1
            try:
                rec = json.loads(dst.read_text())
                self.io.reads += 1
                if not isinstance(rec, dict):
                    raise ValueError("not a request record")
                # The file's name is the id the response is written and
                # the claim released under: a hand-dropped record that
                # states none is answered there, not under a made-up id.
                rec.setdefault("id", path.stem)
                out.append(rec)
            except (OSError, ValueError):
                # Torn request (a foreign client wrote requests/<id>.json
                # without the tmp+rename discipline and died mid-write).
                # Leaving the claim in place would WEDGE admission: the
                # next recover_claimed() moves it back to requests/,
                # claim() re-claims it, forever. Answer it with an error
                # response instead — the id is the filename — which both
                # unblocks any waiting client and clears the claim.
                self.respond(
                    path.stem, {"id": path.stem, "error": "torn request"}
                )
                continue
        return out

    def recover_claimed(self) -> int:
        """Move claims a dead engine left behind back into ``requests/``
        (skipping single-record claims that already have a response;
        batch files are marked ``.recovered`` so re-claim dedups their
        records the same way). Returns how many records were recovered;
        call once at engine startup."""
        n = 0
        try:
            self.io.scans += 1
            stuck = list(self.claimed.iterdir())
        except FileNotFoundError:
            return n
        for path in stuck:
            if path.suffix == BATCH_SUFFIX:
                try:
                    recs, _ = decode_frames(path.read_bytes())
                    self.io.reads += 1
                except OSError:
                    recs = []
                stem = path.name[: -len(BATCH_SUFFIX)]
                if not stem.endswith(RECOVERED_MARK):
                    stem += RECOVERED_MARK
                try:
                    os.rename(path, self.requests / (stem + BATCH_SUFFIX))
                    self.io.renames += 1
                    n += len(recs)
                except FileNotFoundError:
                    continue
                continue
            if path.suffix != ".json":
                continue
            if (self.responses / path.name).exists():
                path.unlink(missing_ok=True)
                self.io.unlinks += 1
                continue
            try:
                os.rename(path, self.requests / path.name)
                self.io.renames += 1
                n += 1
            except FileNotFoundError:
                continue
        return n

    def _release_claim(self, request_id: str) -> None:
        """Clear the claimed-side record for a responded request —
        the single ``.json`` claim, or the rid's slot in its batch
        (the batch file is unlinked when its LAST record responds)."""
        batch = self._rid_batch.pop(request_id, None)
        if batch is not None:
            pending = self._batch_pending.get(batch)
            if pending is not None:
                pending.discard(request_id)
                if not pending:
                    del self._batch_pending[batch]
                    batch.unlink(missing_ok=True)
                    self.io.unlinks += 1
            return
        claimed = self.claimed / f"{request_id}.json"
        try:
            claimed.unlink()
            self.io.unlinks += 1
        except FileNotFoundError:
            pass

    def respond(self, request_id: str, record: dict) -> None:
        tmp = self.responses / f".{request_id}.tmp"
        tmp.write_text(json.dumps(record))
        self.io.creates += 1
        os.rename(tmp, self.responses / f"{request_id}.json")
        self.io.renames += 1
        self._release_claim(request_id)

    def respond_once(self, request_id: str, record: dict) -> bool:
        """Publish a response ONLY if none exists yet; returns whether
        this call won. ``os.link`` is the exclusivity primitive (it
        fails with EEXIST where rename silently overwrites), so two
        racing publishers — a restarted router re-driving a request
        whose first copy already answered — can never both land: the
        loser's record is discarded and the client sees ONE response.
        """
        dst = self.responses / f"{request_id}.json"
        tmp = self.responses / f".{request_id}.{os.getpid()}.tmp"
        tmp.write_text(json.dumps(record))
        self.io.creates += 1
        try:
            os.link(tmp, dst)
            won = True
        except FileExistsError:
            won = False
        finally:
            tmp.unlink(missing_ok=True)
        self.io.links += 1
        self.io.unlinks += 1
        if won:
            self._release_claim(request_id)
        return won

    def has_response(self, request_id: str) -> bool:
        return (self.responses / f"{request_id}.json").exists()

    def read_response(self, request_id: str) -> Optional[dict]:
        """The response record if published and parseable, else None."""
        try:
            rec = json.loads(
                (self.responses / f"{request_id}.json").read_text()
            )
            self.io.reads += 1
            return rec
        except (OSError, json.JSONDecodeError):
            return None

    def drain_responses(self) -> List[dict]:
        """ONE directory scan returning every parseable response record
        (the router's batch collection primitive: O(responses) per
        call instead of one stat-probe per in-flight request per pass).
        Records are NOT consumed — the caller publishes then unlinks."""
        out: List[dict] = []
        try:
            self.io.scans += 1
            entries = list(self.responses.iterdir())
        except FileNotFoundError:
            return out
        for p in entries:
            if p.suffix != ".json":
                continue
            try:
                rec = json.loads(p.read_text())
                self.io.reads += 1
            except (OSError, json.JSONDecodeError):
                continue
            if isinstance(rec, dict):
                out.append(rec)
        return out

    def cancel(self, request_id: str) -> None:
        """Best-effort retraction of an unserved request: removes it
        from requests/ and claimed/ (the router pulls a dead replica's
        copy back this way before re-routing — whichever state the
        crash left it in). A record inside a BATCH file cannot be
        retracted individually; exactly-once is preserved anyway by
        ``respond_once`` at the publication point (a batch record the
        dead replica's successor re-serves loses the publication race)."""
        for d in (self.requests, self.claimed):
            (d / f"{request_id}.json").unlink(missing_ok=True)
            self.io.unlinks += 1

    def sweep_stale(
        self,
        max_age_s: float = 60.0,
        response_ttl_s: Optional[float] = None,
    ) -> int:
        """GC for debris that would otherwise accumulate forever:

        - a ``.tmp`` that outlived ``max_age_s`` belongs to a writer
          that died between write and rename — it will never be renamed
          into place and must not sit in the admission scan forever;
        - with ``response_ttl_s`` set, response records older than it
          are reaped (long-lived serving jobs otherwise leak one file
          per request served — the client had its whole TTL to read);
        - an EMPTY stray subdirectory aged past ``max_age_s`` under any
          spool dir is removed (debris from foreign per-request-dir
          layouts or interrupted tooling).

        Swept on the same cadence the store sweeps ITS stale tmps.
        Returns how many entries were removed."""
        n = 0
        # invariant: waived — compared against st_mtime of files other processes wrote; wall clock is the shared axis
        now = time.time()
        # invariant: waived — st_mtime cutoffs; same cross-process wall-clock axis as above
        cutoff = now - max_age_s
        resp_cutoff = (
            # invariant: waived — st_mtime cutoff; cross-process wall-clock axis
            now - response_ttl_s if response_ttl_s is not None else None
        )
        for d in (self.requests, self.claimed, self.responses):
            try:
                self.io.scans += 1
                entries = list(d.iterdir())
            except FileNotFoundError:
                continue
            for p in entries:
                try:
                    st = p.stat()
                except FileNotFoundError:
                    continue
                if p.is_dir():
                    if st.st_mtime < cutoff:
                        try:
                            p.rmdir()  # only succeeds when empty
                            n += 1
                            self.io.unlinks += 1
                        except OSError:
                            pass
                    continue
                if p.suffix == ".tmp":
                    if st.st_mtime < cutoff:
                        p.unlink(missing_ok=True)
                        n += 1
                        self.io.unlinks += 1
                    continue
                if (
                    resp_cutoff is not None
                    and d is self.responses
                    and p.suffix == ".json"
                    and st.st_mtime < resp_cutoff
                ):
                    p.unlink(missing_ok=True)
                    n += 1
                    self.io.unlinks += 1
        return n

    def pending_count(self) -> int:
        """Unclaimed spool files plus carried batch records. A batch
        file counts as ONE regardless of its record count (an exact
        count would cost a read per batch — this is a telemetry gauge,
        not an accounting surface)."""
        try:
            self.io.scans += 1
            return len(self._carry) + sum(
                1
                for p in self.requests.iterdir()
                if p.suffix in (".json", BATCH_SUFFIX)
            )
        except FileNotFoundError:
            return len(self._carry)
