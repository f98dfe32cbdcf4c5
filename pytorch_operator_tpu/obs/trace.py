"""Span recording to per-process JSONL ring files + Chrome-trace merge.

Write side: :class:`SpanRecorder` keeps one tuple per span in a bounded
in-memory buffer and encodes + writes them in :meth:`~SpanRecorder.flush`
(also ``close()``, ``atexit`` and when the buffer fills) — never inside a
span's exit, which sits on the hot path. A record is
``{"name", "cat", "ph": "X", "ts", "dur", "pid", "tid", "id", "parent",
"args"}`` with ``ts``/``dur`` in microseconds (the Chrome trace event
format, so the merged output loads in Perfetto / ``chrome://tracing``
unmodified); ``id`` numbers the span within its process and ``parent``
is the ``id`` of the span open around it on the same thread, so a
layer's self time is its span minus its children. Files are
``$TPUJOB_TRACE_DIR/<proc>-<pid>.trace.jsonl``, each a ring: past
``max_bytes`` it rotates once (``.1`` generation kept, older dropped),
so a week-long daemon cannot fill the disk with spans.

Enablement is the ``TPUJOB_TRACE_DIR`` env knob, injected per replica
by runtime/env.py and read once per process: with it unset,
:func:`tracer` caches None and :func:`span` returns a shared
nullcontext — no I/O, no allocation. The ``bench_smoke`` lane pins that
a tracing-disabled step loop emits ZERO span records.

One clock with the device: :func:`span` also enters a
``jax.profiler.TraceAnnotation`` of the same name and arguments while a
``jax.profiler`` session is recording (a workload's ``--profile-dir``,
the benchmark's ``--trace 1``), whatever ``TPUJOB_TRACE_DIR`` says — the
profiler then places the program's spans and the device's operations on
one axis. This module never imports JAX: it looks for it in
``sys.modules``, so the supervisor and the CLI stay off the chip.

File timestamps are ``time.time()`` (wall clock — all replicas of a
local world share it, and it is the same clock the progress heartbeats
carry, so the multi-host merger aligns skewed hosts by matching each
replica's heartbeat ``ts`` against the supervisor's fold time). Each
file opens with a ``clock_sync`` metadata record carrying both the wall
clock and ``perf_counter`` so sub-ms skew is reconstructable.

Read side: :func:`load_span_file` skips torn/foreign lines (a
SIGKILLed writer tears its last line and loses its buffered tail — the
ring-file tests pin that the merger survives it);
:func:`merge_trace_files` folds many span files into one
``{"traceEvents": [...]}`` document; :func:`span_self_times` reads
``id``/``parent`` for each span name's self time (``tpujob trace``
prints it).
"""

from __future__ import annotations

import atexit
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Dict, Iterable, List, Optional

ENV_VAR = "TPUJOB_TRACE_DIR"

# Ring size per generation; two generations (current + .1) are kept.
# Overridable per process via TPUJOB_TRACE_RING_BYTES — threaded from
# spec.observability.trace_ring_bytes by runtime/env.py (a long soak
# run wants deeper rings; a tiny CI world wants smaller ones).
DEFAULT_MAX_BYTES = 8 << 20
RING_BYTES_ENV = "TPUJOB_TRACE_RING_BYTES"

# Buffer bound: spans wait in memory, un-encoded, until flush() (the
# serve loop's report, the trainer's heartbeat, the supervisor's pass)
# or until this many have gathered, whichever is first; a crash loses
# at most that tail. Overridable via TPUJOB_TRACE_FLUSH_EVERY
# (spec.observability.trace_flush_every).
FLUSH_EVERY = 256
FLUSH_EVERY_ENV = "TPUJOB_TRACE_FLUSH_EVERY"


def _env_int(name: str, default: int) -> int:
    """A positive int env override, or the default (malformed or
    non-positive values must never break span recording)."""
    raw = os.environ.get(name, "")
    try:
        v = int(raw)
    except ValueError:
        return default
    return v if v > 0 else default

_NULL = contextlib.nullcontext()

# Process-global recorder, resolved lazily from the env once.
_TRACER: Optional["SpanRecorder"] = None
_RESOLVED = False
_LOCK = threading.Lock()

# Total span records emitted by this process (across recorders) — the
# bench_smoke "zero step-path spans when disabled" pin reads this.
_RECORDS = 0


def _default_process_name() -> str:
    rtype = os.environ.get("TPUJOB_REPLICA_TYPE")
    if rtype:
        idx = os.environ.get("TPUJOB_REPLICA_INDEX", "0")
        return f"{rtype.lower()}-{idx}"
    return "supervisor"


def tracer() -> Optional["SpanRecorder"]:
    """The process recorder, or None when ``TPUJOB_TRACE_DIR`` is unset
    or empty. Resolved once; :func:`reset_tracer` re-reads (tests)."""
    global _TRACER, _RESOLVED
    if _RESOLVED:
        return _TRACER
    with _LOCK:
        if not _RESOLVED:
            d = os.environ.get(ENV_VAR, "")
            _TRACER = (
                SpanRecorder(
                    d,
                    _default_process_name(),
                    max_bytes=_env_int(RING_BYTES_ENV, DEFAULT_MAX_BYTES),
                    flush_every=_env_int(FLUSH_EVERY_ENV, FLUSH_EVERY),
                )
                if d
                else None
            )
            _RESOLVED = True
    return _TRACER


def trace_enabled() -> bool:
    return tracer() is not None


def reset_tracer() -> None:
    """Close and forget the process recorder so the next :func:`tracer`
    call re-reads the env — tests and the CLI's ``--trace`` flag (which
    sets the env after import time) use this."""
    global _TRACER, _RESOLVED
    with _LOCK:
        if _TRACER is not None:
            _TRACER.close()
        _TRACER, _RESOLVED = None, False


_ANNOTATION = None  # jax.profiler.TraceAnnotation, once this process has JAX


def _annotation():
    """``jax.profiler.TraceAnnotation`` if this process has imported JAX
    by itself (looked up, never imported: the supervisor and the CLI use
    this module and must not load JAX or touch the chip)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        jax = sys.modules.get("jax")
        _ANNOTATION = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    return _ANNOTATION


def span(name: str, cat: str = "span", **args):
    """Context manager recording one complete span — THE call sites
    sprinkle through the stack. Goes to the process recorder when
    ``TPUJOB_TRACE_DIR`` is set and, as a ``TraceAnnotation`` of the same
    name and arguments, into a ``jax.profiler`` session when one is
    recording. Neither: returns a shared nullcontext (no allocation)."""
    rec = tracer()
    ann = _annotation()
    if ann is not None and not ann.is_enabled():
        ann = None
    if rec is None:
        return _NULL if ann is None else ann(name, **args)
    return _Span(rec, name, cat, args, None if ann is None else ann(name, **args))


def instant(name: str, cat: str = "span", **args) -> None:
    """Zero-duration marker event (restarts, kills, fault injections)."""
    rec = tracer()
    if rec is not None:
        rec.emit(name, cat, time.time(), 0.0, **args)


def flush() -> None:
    """Write the process recorder's buffered spans out — for callers
    that are paying a write anyway (a periodic report, a heartbeat)."""
    rec = tracer()
    if rec is not None:
        rec.flush()


def records_emitted() -> int:
    """Span records emitted by this process so far (0 when disabled —
    the zero-overhead invariant the bench_smoke lane asserts)."""
    return _RECORDS


# Category for every serve-path request hop (enqueue → claim →
# dispatch → ring/spool transit → slot wait → decode → respond →
# publish). One cat so `tpujob trace --request` and the why TTFT
# attribution can select the request waterfall without a name list.
SERVE_CAT = "serve"


def serve_span(name: str, ts: float, dur_s: float, **args) -> None:
    """One serve-path hop span with EXPLICIT endpoints.

    The request path measures hops with its own clocks (a queue wait
    starts at the client's submit wall time, a ring transit at the
    sender's stamp), so the context-manager form can't express them.
    Disabled: one cached-None check, nothing else — the serve-path
    zero-overhead pin counts on call sites computing their args only
    after checking :func:`tracer` themselves, or tolerating the cost
    of a few float subtractions.
    """
    rec = tracer()
    if rec is not None:
        rec.emit(name, SERVE_CAT, ts, dur_s, **args)


# The ids of the spans open on this thread, innermost last.
_OPEN = threading.local()


class _Span:
    """One open span of :func:`span`: its id goes on the thread's stack
    so that spans opened inside it name it as their parent."""

    __slots__ = ("rec", "name", "cat", "args", "ann", "sid", "parent", "t_wall", "t0")

    def __init__(self, rec, name, cat, args, ann=None):
        self.rec, self.name, self.cat, self.args, self.ann = rec, name, cat, args, ann

    def __enter__(self):
        stack = _OPEN.__dict__.setdefault("ids", [])
        self.parent = stack[-1] if stack else None
        self.sid = next(self.rec._ids)
        stack.append(self.sid)
        if self.ann is not None:
            self.ann.__enter__()
        self.t_wall = time.time()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        _OPEN.ids.pop()
        self.rec._append(self.name, self.cat, self.t_wall, dur, self.sid, self.parent, self.args)
        return False


class SpanRecorder:
    """Buffers span records and appends them to one per-process JSONL
    ring file.

    ``emit`` (a span's exit) appends one tuple to a list under the lock
    and nothing else; the JSON encoding and the write happen in
    ``flush()`` — which the callers that already pay a write call (the
    serve loop's report, the trainer's heartbeat), as do ``close()`` and
    ``atexit`` — or when ``flush_every`` records have gathered. A crash
    therefore loses the buffered tail and can tear the last written line
    — the merge side (:func:`load_span_file`) skips torn lines by
    contract.
    """

    def __init__(
        self,
        trace_dir,
        process_name: Optional[str] = None,
        max_bytes: int = DEFAULT_MAX_BYTES,
        flush_every: int = FLUSH_EVERY,
    ):
        self.trace_dir = Path(trace_dir)
        self.trace_dir.mkdir(parents=True, exist_ok=True)
        self.process_name = process_name or _default_process_name()
        self.pid = os.getpid()
        self.path = self.trace_dir / f"{self.process_name}-{self.pid}.trace.jsonl"
        self.max_bytes = max_bytes
        self.flush_every = max(1, flush_every)
        self.records = 0
        self._lock = threading.Lock()
        self._f = open(self.path, "ab")
        self._buf: list = []
        self._ids = itertools.count(1)
        self._write_header()
        # Normal process exit writes the buffered tail; a SIGKILL loses
        # it, which the merge side tolerates by contract.
        atexit.register(self.close)

    def _process_meta(self) -> dict:
        return {
            "ph": "M",
            "name": "process_name",
            "pid": self.pid,
            "tid": 0,
            "args": {"name": self.process_name},
        }

    def _write_header(self) -> None:
        # Metadata the merger turns into Perfetto process names, plus
        # the clock-sync pair for cross-host alignment.
        meta = [
            self._process_meta(),
            {
                "ph": "M",
                "name": "clock_sync",
                "pid": self.pid,
                "tid": 0,
                "args": {
                    "unix_ts": time.time(),
                    "perf_counter": time.perf_counter(),
                    "job": os.environ.get("TPUJOB_KEY", ""),
                },
            },
        ]
        with self._lock:
            for m in meta:
                self._f.write(json.dumps(m).encode() + b"\n")
            self._f.flush()

    def emit(
        self, name: str, cat: str, ts: float, dur_s: float, **args
    ) -> None:
        """Record one complete span with explicit endpoints; ``ts`` is
        wall-clock seconds of the span START, ``dur_s`` its duration."""
        self._append(name, cat, ts, dur_s, next(self._ids), None, args)

    def _append(self, name, cat, ts, dur_s, sid, parent, args) -> None:
        global _RECORDS
        item = (name, cat, ts, dur_s, threading.get_ident() & 0x7FFFFFFF, sid, parent, args)
        with self._lock:
            if self._f.closed:
                return
            self._buf.append(item)
            self.records += 1
            _RECORDS += 1
            if len(self._buf) >= self.flush_every:
                self._drain()

    def _drain(self) -> None:
        """Encode and write the buffered records, under the held lock."""
        buf, self._buf = self._buf, []
        for name, cat, ts, dur_s, tid, sid, parent, args in buf:
            rec = {
                "name": name,
                "cat": cat,
                "ph": "X",
                "ts": round(ts * 1e6, 1),
                "dur": round(dur_s * 1e6, 1),
                "pid": self.pid,
                "tid": tid,
                "id": sid,
            }
            if parent is not None:
                rec["parent"] = parent
            if args:
                rec["args"] = args
            line = json.dumps(rec).encode() + b"\n"
            self._maybe_rotate(len(line))
            self._f.write(line)
        self._f.flush()

    def _maybe_rotate(self, incoming: int) -> None:
        """Ring rotation under the held lock: current generation moves
        to ``.1`` (replacing the previous one), a fresh file starts."""
        try:
            if self._f.tell() + incoming <= self.max_bytes:
                return
            self._f.flush()
            self._f.close()
            self.path.replace(self.path.with_suffix(".jsonl.1"))
            self._f = open(self.path, "ab")
        except OSError:
            # A full disk must never take the traced process down.
            if self._f.closed:
                self._f = open(os.devnull, "ab")
        # Re-emit the header so the new generation is self-describing.
        self._f.write(json.dumps(self._process_meta()).encode() + b"\n")

    def span(self, name: str, cat: str = "span", **args):
        return _Span(self, name, cat, args)

    def flush(self) -> None:
        with self._lock:
            if not self._f.closed and self._buf:
                self._drain()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._drain()
                self._f.close()


# ---- merge / export ----


def load_span_file(path) -> List[dict]:
    """Parse one span JSONL file into event dicts. Torn last lines
    (crashed writer), foreign lines, and records missing the required
    Chrome-trace fields are skipped — the trace dir is written by live
    processes and read after kills."""
    out: List[dict] = []
    try:
        data = Path(path).read_bytes()
    except OSError:
        return out
    for line in data.splitlines():
        if not line.strip():
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue  # torn or foreign
        if not isinstance(rec, dict) or "ph" not in rec or "name" not in rec:
            continue
        if rec["ph"] == "X" and ("ts" not in rec or "dur" not in rec):
            continue
        out.append(rec)
    return out


def span_files(trace_dir, include_rotated: bool = True) -> List[Path]:
    """The span files (current + rotated generations) directly under
    ``trace_dir``, stable order."""
    d = Path(trace_dir)
    if not d.is_dir():
        return []
    pats = ["*.trace.jsonl"] + (["*.trace.jsonl.1"] if include_rotated else [])
    return sorted(p for pat in pats for p in d.glob(pat))


def merge_trace_files(paths: Iterable, clock_offsets: Optional[Dict] = None) -> dict:
    """Fold span files into one Chrome-trace JSON document.

    ``clock_offsets`` maps path -> seconds to ADD to that file's
    timestamps — the cross-host alignment hook, now fed by the
    heartbeat-matching estimator (obs/clock.py:estimate_job_offsets via
    ``tpujob trace``/``tpujob why``; local worlds share a clock so the
    default is 0 everywhere). Each corrected file gets a
    ``clock_sync_correction`` metadata record naming the applied offset
    so a merged trace is self-describing about its own alignment.
    Events are sorted by ts; metadata records keep their file order.
    The result loads directly in Perfetto (https://ui.perfetto.dev) or
    chrome://tracing."""
    meta: List[dict] = []
    events: List[dict] = []
    for p in paths:
        off_s = (clock_offsets or {}).get(p, 0.0)
        off_us = 1e6 * off_s
        file_pid = None
        for rec in load_span_file(p):
            if file_pid is None:
                file_pid = rec.get("pid", 0)
            if rec.get("ph") == "M":
                if rec not in meta:
                    meta.append(rec)
            else:
                if off_us:
                    rec = dict(rec)
                    rec["ts"] = rec.get("ts", 0) + off_us
                events.append(rec)
        if off_us:
            meta.append(
                {
                    "ph": "M",
                    "name": "clock_sync_correction",
                    "pid": file_pid or 0,
                    "tid": 0,
                    "args": {
                        "file": os.path.basename(str(p)),
                        "offset_s": round(off_s, 6),
                    },
                }
            )
    events.sort(key=lambda r: r.get("ts", 0))
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def span_self_times(events: Iterable[dict]) -> Dict[str, dict]:
    """``{name: {"count", "total_ms", "self_ms"}}`` over the complete
    spans of ``events``: a span's self time is its duration less that
    of the spans naming it as ``parent`` (ids count per process, so the
    key is ``(pid, id)``). Spans without an ``id`` (older files) and
    the explicit-endpoint hops, which name no parent, count whole."""
    spans = [e for e in events if e.get("ph") == "X"]
    children: Dict[tuple, float] = {}
    for e in spans:
        if e.get("parent") is not None:
            key = (e.get("pid"), e["parent"])
            children[key] = children.get(key, 0.0) + e.get("dur", 0.0)
    out: Dict[str, dict] = {}
    for e in spans:
        row = out.setdefault(e["name"], {"count": 0, "total_ms": 0.0, "self_ms": 0.0})
        dur = e.get("dur", 0.0)
        row["count"] += 1
        row["total_ms"] += dur / 1e3
        row["self_ms"] += max(0.0, dur - children.get((e.get("pid"), e.get("id")), 0.0)) / 1e3
    return out
