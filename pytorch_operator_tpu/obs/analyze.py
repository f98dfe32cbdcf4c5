"""``tpujob why`` — the cross-host postmortem engine.

The flight recorder (obs/trace, obs/metrics) answers "where did the
time go" to a human staring at Perfetto; production pre-training stacks
treat AUTOMATED diagnosis of stragglers, stalls, and checkpoint lag as
a first-class feature (TorchTitan, arXiv:2410.06511 — and the TPU-pod
concurrency study shows host-level skew and input-feed stalls dominate
real regressions). This module turns the recorded artifacts into a
diagnosis:

1. **Align** — per-replica clock offsets from the heartbeat observation
   log (obs/clock.py), so records from skewed hosts land on one causal
   axis (the supervisor's clock, which also stamps events and kills).
2. **Join** — one :class:`Timeline` from the per-replica status records
   (every kind, full history — this is offline, not the per-pass tail
   fold), the job's event sink, and (when recorded) the merged span
   files.
3. **Detect** — the SHARED rule pass (obs/rules.py — the same code the
   live watch evaluates every supervisor pass) over the timeline; each
   :class:`~pytorch_operator_tpu.obs.rules.Finding` cites the exact
   records/spans that evidence it. Per-job threshold overrides come
   from the stored ``spec.observability.alerts`` block, so offline and
   live judge by the same bar.
4. **Render** — a terminal report (:func:`render_report`) and a
   machine-readable dict (:func:`analyze`) for ``--out report.json``,
   including the live engine's alert history (what was already firing
   before death — obs/watch.py's append-only per-job alert log).

Everything runs strictly OFFLINE from recorded artifacts: analysis adds
zero span/metric calls to the step path (the bench_smoke lane pins it).
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, List, Optional

from .clock import OffsetEstimate, estimate_job_offsets, offsets_for_trace_files
from .metrics import parse_exemplars
from .rules import (  # noqa: F401  (re-exported: the pre-refactor public surface)
    DEFAULT_THRESHOLDS,
    DETECTORS,
    SEVERITY_ORDER as _SEVERITY_ORDER,
    Finding,
    Thresholds,
    detect_checkpoint_lag,
    detect_feed_stall,
    detect_heartbeat_silence,
    detect_step_time_regression,
    detect_straggler,
    run_detectors,
    thresholds_from_overrides,
)
from .trace import load_span_file, span_files

# Back-compat aliases: the detector thresholds were module constants
# before the rules moved to obs/rules.py (tests and external callers
# pinned them); the Thresholds dataclass is the source of truth now.
REGRESSION_FACTOR = DEFAULT_THRESHOLDS.regression_factor
REGRESSION_MIN_MS = DEFAULT_THRESHOLDS.regression_min_ms
REGRESSION_MIN_BASELINE = DEFAULT_THRESHOLDS.regression_min_baseline
REGRESSION_MIN_RECENT = DEFAULT_THRESHOLDS.regression_min_recent
FEED_STALL_SHARE = DEFAULT_THRESHOLDS.feed_stall_share
FEED_STALL_MIN_MS = DEFAULT_THRESHOLDS.feed_stall_min_ms
FEED_MIN_SAMPLES = DEFAULT_THRESHOLDS.feed_min_samples
CKPT_LAG_CADENCES = DEFAULT_THRESHOLDS.ckpt_lag_cadences
CKPT_QUEUE_GROWTH_COMMITS = DEFAULT_THRESHOLDS.ckpt_queue_growth_commits
SILENCE_FACTOR = DEFAULT_THRESHOLDS.silence_factor
SILENCE_MIN_S = DEFAULT_THRESHOLDS.silence_min_s
STRAGGLER_FACTOR = DEFAULT_THRESHOLDS.straggler_factor
STRAGGLER_MIN_SAMPLES = DEFAULT_THRESHOLDS.straggler_min_samples


class Timeline:
    """The per-job causal join: status records per replica, events, and
    spans, all on the supervisor's clock. The offline
    :class:`~pytorch_operator_tpu.obs.rules.TimelineView` — detectors
    read this; nothing here touches the live system."""

    def __init__(
        self,
        key: str,
        clock: Dict[str, OffsetEstimate],
        progress: Dict[str, List[dict]],
        records: Dict[str, List[dict]],
        events: List[dict],
        spans: List[dict],
        window_s: Optional[float] = None,
    ):
        self.key = key
        self.clock = clock
        # {replica: [progress records]}, each record sanitized floats
        # with an ``aligned_ts`` added; sorted by aligned_ts.
        self.progress = progress
        # {kind: [records across replicas]} for the non-progress kinds.
        self.records = records
        self.events = events
        self.spans = spans
        ts_all = [
            r["aligned_ts"] for rs in progress.values() for r in rs
        ] + [float(e.get("timestamp", 0.0)) for e in events]
        self.t_end = max(ts_all) if ts_all else 0.0
        self.t_start = min(ts_all) if ts_all else 0.0
        self.window_s = window_s

    def in_window(self, ts: float) -> bool:
        if self.window_s is None:
            return True
        return ts >= self.t_end - self.window_s

    def all_progress(self) -> List[dict]:
        out = [r for rs in self.progress.values() for r in rs]
        out.sort(key=lambda r: r["aligned_ts"])
        return out

    def beat_interval(self) -> float:
        """Median inter-beat gap pooled across replicas (the cadence
        silence is judged against)."""
        gaps: List[float] = []
        for rs in self.progress.values():
            for a, b in zip(rs, rs[1:]):
                gaps.append(b["aligned_ts"] - a["aligned_ts"])
        return _median(gaps) if gaps else 0.0

    def silence_reference(self) -> float:
        """Offline silence is judged against the gang's NEWEST beat
        ("someone kept beating, someone stopped") — never against the
        recording's end, which would flag every replica of a healthy
        finished job."""
        last = [rs[-1]["aligned_ts"] for rs in self.progress.values() if rs]
        return max(last) if last else 0.0

    def find_event(self, *reasons: str) -> Optional[dict]:
        for e in self.events:
            if e.get("reason") in reasons:
                return e
        return None

    def find_step_span(self, replica: str, step: int) -> Optional[dict]:
        for s in self.spans:
            if (
                s.get("name") == "step"
                and s.get("args", {}).get("step") == step
                and s.get("_replica", replica) == replica
            ):
                return s
        return None


def _median(vals: List[float]) -> float:
    from .rules import _median as m

    return m(vals)


# ---- timeline construction ----


def _read_status_records(status_dir) -> Dict[str, List[dict]]:
    """Full parse of every replica status file: {replica: [records]},
    file order preserved (append order == causal order per replica).
    Torn/foreign lines skipped, as everywhere on the read side."""
    d = Path(status_dir)
    out: Dict[str, List[dict]] = {}
    if not d.is_dir():
        return out
    for p in sorted(d.glob("*.jsonl")):
        recs: List[dict] = []
        try:
            data = p.read_bytes()
        except OSError:
            continue
        for line in data.splitlines():
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict) or "event" not in rec:
                continue
            recs.append(rec)
        if recs:
            out[p.stem] = recs
    return out


def build_timeline(
    state_dir, key: str, window_s: Optional[float] = None
) -> Timeline:
    """Join the recorded artifacts for one job onto the aligned clock.

    Offline by construction: reads the status dir, event sink, clock
    log, and span files; writes nothing, emits no spans or metrics."""
    from ..controller.events import load_merged_events
    from ..controller.store import key_to_fs

    state = Path(state_dir)
    fs = key_to_fs(key)

    clock = estimate_job_offsets(state, key)

    def aligned(replica: str, ts: float) -> float:
        est = clock.get(replica)
        return ts + est.offset_at(ts) if est is not None else ts

    from ..controller.progress import TAILED_KINDS, _sanitize

    raw = _read_status_records(state / "status" / fs)
    progress: Dict[str, List[dict]] = {}
    records: Dict[str, List[dict]] = {}
    for replica, recs in raw.items():
        for rec in recs:
            kind = rec.get("event")
            try:
                ts = float(rec.get("ts", 0.0))
            except (TypeError, ValueError):
                continue
            if kind in TAILED_KINDS:
                # The supervisor-fold kinds get the same numeric
                # coercion the live fold applies — one foreign line
                # must not crash a postmortem either.
                clean = _sanitize(rec, kind)
                if clean is None:
                    continue
            else:
                clean = {k: v for k, v in rec.items() if k != "event"}
            clean["replica"] = replica
            clean["ts"] = ts
            clean["aligned_ts"] = aligned(replica, ts)
            if kind == "progress":
                progress.setdefault(replica, []).append(clean)
            else:
                records.setdefault(kind, []).append(clean)
    for rs in progress.values():
        rs.sort(key=lambda r: r["aligned_ts"])
    for rs in records.values():
        rs.sort(key=lambda r: r["aligned_ts"])

    events = load_merged_events(
        state / "events" / (fs + ".events.jsonl")
    )
    # Sharded control plane: the shard event log is GLOBAL (one bounded
    # sink, not one per job) — fold in the hand-offs of THIS job's
    # shard so the postmortem can cite an ownership change ("the
    # supervisor reconciling this job died at t; shard re-claimed at
    # t+ttl").
    events = events + shard_events_for_job(state, key)
    events.sort(key=lambda e: float(e.get("timestamp", 0.0)))

    # Spans (optional): replica files aligned by the estimator, the
    # supervisor's own files are already in the reference frame.
    spans: List[dict] = []
    for root in (state / "trace" / fs, state / "trace"):
        paths = span_files(root)
        offsets = offsets_for_trace_files(paths, clock)
        for p in paths:
            off_us = 1e6 * offsets.get(p, 0.0)
            replica = _replica_of_trace_file(p)
            for rec in load_span_file(p):
                if rec.get("ph") != "X":
                    continue
                if off_us:
                    rec = dict(rec)
                    rec["ts"] = rec.get("ts", 0) + off_us
                if replica:
                    rec["_replica"] = replica
                spans.append(rec)
    spans.sort(key=lambda r: r.get("ts", 0))

    return Timeline(
        key=key,
        clock=clock,
        progress=progress,
        records=records,
        events=events,
        spans=spans,
        window_s=window_s,
    )


def _replica_of_trace_file(path) -> Optional[str]:
    from .clock import _trace_file_replica

    return _trace_file_replica(path)


def shard_events_for_job(state_dir, key: str) -> List[dict]:
    """Shard hand-off events affecting ``key``'s shard, from the global
    shard event sink (controller/leases.py SHARD_EVENT_KEY). Empty when
    the control plane never ran sharded. The job's shard is resolved
    exactly like the supervisor does: spec pin if set, else key hash."""
    from ..controller.events import load_merged_events
    from ..controller.leases import SHARD_EVENT_KEY, shard_of_key
    from ..controller.store import JobStore, key_to_fs

    state = Path(state_dir)
    from ..controller.leases import read_shard_config

    num_shards = read_shard_config(state)
    if not num_shards:
        return []
    pin = None
    job = JobStore(persist_dir=state / "jobs").get(key)
    if job is not None:
        pin = job.spec.run_policy.scheduling_policy.shard
    shard = shard_of_key(key, num_shards, pin)
    needle = f"shard {shard} "
    out = []
    for ev in load_merged_events(
        state / "events" / (key_to_fs(SHARD_EVENT_KEY) + ".events.jsonl")
    ):
        msg = str(ev.get("message", ""))
        if needle in msg or f"shard(s) [{shard}]" in msg:
            ev = dict(ev)
            ev["shard"] = shard
            out.append(ev)
    return out


# ---- the engine ----

#: The five stages a traced request crosses between enqueue and its
#: decode — each maps the serve-path span names that account for it.
_TTFT_HOPS = (
    ("queue_wait", ("claim",)),
    ("lane_handoff", ("dispatch",)),
    ("transit", ("ring_transit", "spool_transit")),
    ("slot_wait", ("slot_wait",)),
    ("decode", ("decode",)),
)


def ttft_attribution(spans: List[dict]) -> Optional[dict]:
    """Where time-to-first-token went, pooled over every traced request
    in the window: the serve-path hop spans (cat ``serve``) bucketed
    into the stages a request crosses between client enqueue and its
    decode blocks. ``dominant`` names the hop with the largest mean —
    the one sentence the report leads with. None when the job recorded
    no serve spans (tracing off, or a training job)."""
    from .rules import _quantile

    by_name: Dict[str, List[float]] = {}
    rids = set()
    for s in spans:
        if s.get("cat") != "serve":
            continue
        by_name.setdefault(str(s.get("name", "?")), []).append(
            s.get("dur", 0) / 1e3
        )
        rid = (s.get("args") or {}).get("rid")
        if rid:
            rids.add(rid)
    hops: Dict[str, dict] = {}
    for hop, names in _TTFT_HOPS:
        vals = [v for n in names for v in by_name.get(n, [])]
        if not vals:
            continue
        hops[hop] = {
            "n": len(vals),
            "total_ms": round(sum(vals), 3),
            "mean_ms": round(sum(vals) / len(vals), 3),
            "p99_ms": round(_quantile(vals, 0.99), 3),
        }
    if not hops:
        return None
    dominant = max(hops, key=lambda h: hops[h]["mean_ms"])
    return {"requests": len(rids), "hops": hops, "dominant": dominant}


def job_thresholds(job) -> Thresholds:
    """The detector thresholds for one job: defaults overridden by its
    ``spec.observability.alerts.thresholds`` block. Shared bar: the
    live watch resolves the SAME way (obs/watch.py)."""
    if job is not None:
        ob = job.spec.observability
        if ob is not None and ob.alerts is not None:
            return thresholds_from_overrides(ob.alerts.thresholds)
    return DEFAULT_THRESHOLDS


def analyze(
    state_dir,
    key: str,
    window_s: Optional[float] = None,
    now: Optional[float] = None,
) -> dict:
    """Run the full postmortem for one job; returns the report dict
    (``tpujob why --out`` writes it verbatim as JSON)."""
    import time as _time

    from ..controller.store import JobStore

    tl = build_timeline(state_dir, key, window_s=window_s)
    job = JobStore(persist_dir=Path(state_dir) / "jobs").get(key)
    phase = None
    restarts = 0
    if job is not None:
        restarts = job.status.restart_count
        for c in reversed(job.status.conditions):
            if c.status:
                phase = c.type.value
                break

    findings = run_detectors(tl, job_thresholds(job))

    # Exemplar cross-links (when a daemon wrote metrics.prom): the p99
    # cell's latest span id per histogram, so the report can say WHICH
    # span landed the tail.
    exemplars: Dict[str, List[dict]] = {}
    # metrics.prom (unsharded) or one metrics-<identity>.prom per
    # sharded supervisor — the job's series live in its owner's file.
    for prom in sorted(Path(state_dir).glob("metrics*.prom")):
        try:
            for name, rows in parse_exemplars(prom.read_text()).items():
                hits = [
                    {"le": labels.get("le", ""), "span_id": span_id,
                     "value": value}
                    for labels, span_id, value in rows
                    if labels.get("job") == key
                ]
                if hits:
                    exemplars.setdefault(name, []).extend(hits)
        except OSError:
            pass

    # The live engine's verdicts (obs/watch.py alert log): what was
    # already pending/firing before the death `why` is explaining —
    # cross-cited so "the watch saw it live" and "the postmortem found
    # it" are one story.
    from .watch import load_alert_log

    alerts = load_alert_log(state_dir, key)

    # The remediation engine's audit trail (controller/remediation.py):
    # every alert→decision→action→outcome the closed loop took (or would
    # have taken, in dry-run) for this job, each citing the triggering
    # alert instance and the fencing token it committed under.
    from ..controller.remediation import load_remediation_log

    remediations = load_remediation_log(state_dir, key)

    # Control-plane ownership history for this job's shard: who was
    # reconciling it, and when that changed (lease expiry after a
    # supervisor death, rebalance, injected drop) — the citation for
    # "nothing reconciled this job between t and t+ttl".
    shard_handoffs = [
        {
            "ts": float(e.get("timestamp", 0.0)),
            "reason": e.get("reason"),
            "message": e.get("message"),
            "shard": e.get("shard"),
        }
        for e in shard_events_for_job(state_dir, key)
        if tl.in_window(float(e.get("timestamp", 0.0)))
    ]

    # Elastic resize history: every world-membership transition the
    # reconciler committed (shrink-in-place, spare promotion, grow-back)
    # plus the worker-side joins/evictions it fenced — the `why` face of
    # the resize-generation protocol.
    _RESIZE_HISTORY_REASONS = {
        "ElasticScaledDown",
        "ElasticScaledUp",
        "ElasticSparePromoted",
        "ElasticResizeJoined",
        "ElasticResizeEvicted",
        "ElasticResizeHealed",
    }
    resize_history = sorted(
        (
            {
                "ts": float(e.get("timestamp", 0.0)),
                "reason": e.get("reason"),
                "message": e.get("message"),
            }
            for e in tl.events
            if e.get("reason") in _RESIZE_HISTORY_REASONS
            and tl.in_window(float(e.get("timestamp", 0.0)))
        ),
        key=lambda r: r["ts"],
    )

    replicas = {
        replica: {
            "beats": len(rs),
            "first_ts": round(rs[0]["aligned_ts"], 6),
            "last_ts": round(rs[-1]["aligned_ts"], 6),
            "last_step": rs[-1].get("step"),
        }
        for replica, rs in sorted(tl.progress.items())
    }

    # What each replica compiled and what it found in the compile cache
    # (runtime/backend.py counts; the first_step and metrics records carry
    # the totals so far): a warm start compiles nothing.
    compiled: Dict[str, dict] = {}
    for kind, at in (("first_step", "to_first_step"), ("metrics", "in_all")):
        for rec in tl.records.get(kind, []):  # sorted by time: the last one stays
            if "programs_compiled" in rec:
                compiled.setdefault(rec["replica"], {})[at] = [
                    rec["programs_compiled"], rec.get("programs_from_cache", 0)
                ]

    # A model whose per-slot state is a recurrence starts a row from zero at
    # each admission (models/nemotron_h.py): the engine's last metrics record
    # has both counts, and they must agree.
    state_resets = {
        rec["replica"]: [rec["prefill_state_resets"], rec.get("admitted", 0)]
        for rec in tl.records.get("metrics", [])
        if "prefill_state_resets" in rec
    }

    # A model whose later layers write no state runs them on a prompt's last
    # token only (models/phi4_flash.py): the tokens they ran on outside the
    # decode dispatches, beside the prompt tokens prefilled.
    cross_tokens = {
        rec["replica"]: [rec["prefill_cross_tokens"], rec.get("prefill_tokens", 0)]
        for rec in tl.records.get("metrics", [])
        if "prefill_cross_tokens" in rec
    }

    # A serving engine's admissions (serving/engine.py): the rounds that
    # admitted, beside the decode dispatches queued behind one with its
    # first token still unread. Equal whenever an admitted row decodes.
    # Then the prompts' chunks, and those that went through the wide program
    # (a long prompt's body, for a model that takes a wide chunk).
    admit_rounds = {
        rec["replica"]: [rec["admit_rounds"], rec.get("decode_behind_admit", 0), rec.get("admitted", 0),
                         rec.get("prefill_chunks", 0), rec.get("prefill_wide_chunks", 0)]
        for rec in tl.records.get("metrics", [])
        if "admit_rounds" in rec
    }

    # The boundaries that queued chunks of a prompt, beside the prompts: 1 a
    # prompt until one is longer than a boundary's budget and its prefill is
    # spread over several (serving/engine.py ``ADMIT_TOKENS``).
    prefill_rounds = {
        rec["replica"]: [rec["prefill_rounds"], rec.get("admitted", 0)]
        for rec in tl.records.get("metrics", [])
        if "prefill_rounds" in rec
    }

    # What a serving engine's decode steps read of the cache slabs beside
    # what their rows had live (serving/engine.py, ops/cache_attention.py):
    # near 1 where each row is read to its own depth, the deepest row's
    # depth over the mean's where every row is read to the deepest.
    slab_reads = {
        rec["replica"]: [rec["decode_attended_positions"], rec["decode_live_positions"]]
        for rec in tl.records.get("metrics", [])
        if rec.get("decode_live_positions")
    }

    # A model that drafts (models/serving.py ``Drafter``): the row-steps that
    # verified a draft, those whose draft was accepted, and the share.
    drafts = {
        rec["replica"]: [rec["mtp_drafts"], rec.get("mtp_accepted", 0), rec.get("mtp_accept_pct")]
        for rec in tl.records.get("metrics", [])
        if rec.get("mtp_drafts")
    }

    return {
        "job": key,
        "generated_at": _time.time() if now is None else now,
        "window_s": window_s,
        "phase": phase,
        "restarts": restarts,
        "clock": {r: est.to_dict() for r, est in sorted(tl.clock.items())},
        "replicas": replicas,
        "programs_compiled": compiled,
        "state_resets": state_resets,
        "cross_tokens": cross_tokens,
        "admit_rounds": admit_rounds,
        "prefill_rounds": prefill_rounds,
        "slab_reads": slab_reads,
        "drafts": drafts,
        "events": len(tl.events),
        "spans": len(tl.spans),
        "exemplars": exemplars,
        "ttft_attribution": ttft_attribution(tl.spans),
        "alerts": alerts,
        "remediations": remediations,
        "shard_handoffs": shard_handoffs,
        "resize_history": resize_history,
        "findings": [f.to_dict() for f in findings],
    }


def _fmt_ev(ev: dict) -> str:
    src = ev.get("source")
    if src == "event":
        return (
            f"event  {ev.get('reason')} @ {ev.get('ts'):.3f}  "
            f"{ev.get('message', '')}"
        )
    if src == "span":
        args = ev.get("args") or {}
        blob = " ".join(f"{k}={v}" for k, v in sorted(args.items()))
        return (
            f"span   {ev.get('name')} @ {ev.get('ts'):.3f} "
            f"dur={ev.get('dur_ms'):.1f}ms {blob}".rstrip()
        )
    if src == "alert":
        return (
            f"alert  {ev.get('rule')} on {ev.get('job')}: "
            f"{ev.get('summary', '')}"
        )
    fields = " ".join(
        f"{k}={ev[k]}"
        for k in ("step", "step_time_ms", "feed_stall_ms", "queue_depth")
        if ev.get(k) is not None
    )
    return (
        f"status {ev.get('kind')} {ev.get('replica')} @ "
        f"{ev.get('ts'):.3f} {fields}".rstrip()
    )


def render_report(report: dict) -> str:
    """The terminal face of the report: findings first (most severe on
    top), each with its evidence; alert history and clock table after;
    '-' free prose kept short — the JSON carries the full detail."""
    lines: List[str] = []
    head = f"tpujob why {report['job']}"
    if report.get("phase"):
        head += f" — {report['phase']} (restarts={report['restarts']})"
    lines.append(head)
    reps = report.get("replicas", {})
    lines.append(
        f"analyzed: {sum(r['beats'] for r in reps.values())} heartbeats "
        f"from {len(reps)} replica(s), {report.get('events', 0)} events, "
        f"{report.get('spans', 0)} spans"
        + (
            f", window {report['window_s']:g}s"
            if report.get("window_s")
            else ""
        )
    )
    clock = report.get("clock", {})
    if clock:
        parts = [
            f"{r} {e['offset_s']:+.3f}s ±{e['residual_s']:.3f} (n={e['n']})"
            for r, e in clock.items()
        ]
        lines.append("clock:    " + "; ".join(parts))
    for replica, counts in sorted(report.get("programs_compiled", {}).items()):
        lines.append(
            f"compiled: {replica} "
            + ", ".join(
                f"{n} program(s) compiled and {hits} from the cache {at.replace('_', ' ')}"
                for at, (n, hits) in counts.items()
            )
        )
    for replica, (resets, admitted) in sorted(report.get("state_resets", {}).items()):
        lines.append(
            f"state:    {replica} {resets} row(s) started from zero state for {admitted} admitted"
            + ("" if resets == admitted else "  <-- these must be equal")
        )
    for replica, (ran, prefilled) in sorted(report.get("cross_tokens", {}).items()):
        lines.append(f"prefill:  {replica} prefill_cross_tokens {ran} beside prefill_tokens {prefilled}")
    for replica, (rounds, behind, admitted, chunks, wide) in sorted(report.get("admit_rounds", {}).items()):
        lines.append(
            f"admits:   {replica} {admitted} admitted in {rounds} round(s), "
            f"the decode dispatch queued behind {behind} of them before a first token was read; "
            f"{wide} of {chunks} prefill chunk(s) wide"
        )
    for replica, (rounds, admitted) in sorted(report.get("prefill_rounds", {}).items()):
        if admitted:
            lines.append(
                f"rounds:   {replica} prefill_rounds {rounds} for {admitted} admitted = {rounds / admitted:.2f} a prompt"
                + ("" if rounds == admitted else " (a prompt longer than a boundary's budget is prefilled over several)")
            )
    for replica, (attended, live) in sorted(report.get("slab_reads", {}).items()):
        lines.append(
            f"slabs:    {replica} decode_attended_positions {attended} over decode_live_positions {live} "
            f"= {attended / live:.2f}"
        )
    for replica, (drafted, accepted, pct) in sorted(report.get("drafts", {}).items()):
        lines.append(
            f"drafts:   {replica} mtp_accepted {accepted} of mtp_drafts {drafted} row-step(s): mtp_accept_pct {pct}"
        )
    alerts = report.get("alerts", [])
    findings = report.get("findings", [])
    ttft = report.get("ttft_attribution")
    if (
        not findings
        and not alerts
        and not ttft
        and not report.get("remediations")
        and not report.get("shard_handoffs")
        and not report.get("resize_history")
    ):
        lines.append("")
        lines.append("no findings — the recorded window looks healthy.")
        return "\n".join(lines)
    if findings:
        lines.append("")
        lines.append(f"FINDINGS ({len(findings)}):")
        for i, f in enumerate(findings, 1):
            lines.append(
                f"{i:3d}. [{f['severity']}] {f['rule']}: {f['summary']}"
            )
            for ev in f.get("evidence", []):
                lines.append(f"       - {_fmt_ev(ev)}")
    else:
        lines.append("")
        lines.append("no findings — the recorded window looks healthy.")
    if ttft:
        # Serve-path hop breakdown (only when request tracing recorded
        # serve spans): which hop is eating time-to-first-token.
        lines.append("")
        lines.append(
            f"TTFT ATTRIBUTION ({ttft.get('requests', 0)} traced "
            f"request(s)) — dominant hop: {ttft.get('dominant', '?')}"
        )
        for hop, _names in _TTFT_HOPS:
            st = ttft.get("hops", {}).get(hop)
            if st is None:
                continue
            lines.append(
                f"  {hop:<12} mean {st['mean_ms']:8.2f}ms  "
                f"p99 {st['p99_ms']:8.2f}ms  "
                f"total {st['total_ms']:9.1f}ms  (n={st['n']})"
            )
    if alerts:
        # What the live engine already said, while the job was running:
        # every firing/resolved transition, oldest first.
        lines.append("")
        lines.append(f"LIVE ALERTS ({len(alerts)} transition(s)):")
        for rec in alerts:
            who = rec.get("replica") or "*"
            lines.append(
                f"  {rec.get('state', '?'):<8} [{rec.get('severity', '?')}] "
                f"{rec.get('rule', '?')} {who} @ "
                f"{float(rec.get('ts', 0.0)):.3f}  "
                f"{rec.get('summary', '')}"
            )
    remediations = report.get("remediations", [])
    if remediations:
        # What the closed loop DID about those alerts: each action cites
        # the causal alert instance so the remediation and the alert read
        # as one story (and dry-run decisions are visibly inert).
        lines.append("")
        lines.append(f"REMEDIATIONS ({len(remediations)} action(s)):")
        for rec in remediations:
            lines.append(
                f"  {rec.get('outcome', '?'):<8} "
                f"{rec.get('action', '?'):<18} gen={rec.get('generation', 0)} "
                f"rule={rec.get('rule', '?')} @ "
                f"{float(rec.get('ts', 0.0)):.3f}  {rec.get('detail', '')}"
            )
            al = rec.get("alert")
            if al:
                lines.append(
                    f"           └ alert [{al.get('severity', '?')}] "
                    f"{al.get('rule', '?')} {al.get('replica') or '*'} "
                    f"fired @ {float(al.get('fired_at') or 0.0):.3f}  "
                    f"{al.get('summary', '')}"
                )
    handoffs = report.get("shard_handoffs", [])
    if handoffs:
        lines.append("")
        lines.append(
            f"SHARD OWNERSHIP ({len(handoffs)} hand-off event(s) for "
            f"shard {handoffs[0].get('shard')}):"
        )
        for rec in handoffs:
            lines.append(
                f"  {rec.get('reason', '?'):<16} @ "
                f"{float(rec.get('ts', 0.0)):.3f}  {rec.get('message', '')}"
            )
    resizes = report.get("resize_history", [])
    if resizes:
        lines.append("")
        lines.append(f"RESIZE HISTORY ({len(resizes)} transition(s)):")
        for rec in resizes:
            lines.append(
                f"  {rec.get('reason', '?'):<20} @ "
                f"{float(rec.get('ts', 0.0)):.3f}  {rec.get('message', '')}"
            )
    return "\n".join(lines)
