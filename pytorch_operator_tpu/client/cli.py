"""The ``tpujob`` CLI — the kubectl+CRD surface of the reference.

Reference mapping (SURVEY.md §7 architecture sketch):

- ``kubectl apply -f job.yaml``   → ``tpujob run job.yaml`` (foreground
  supervise-to-completion) or ``tpujob submit job.yaml`` (queue for a
  running ``tpujob supervisor`` daemon)
- ``kubectl get pytorchjobs``     → ``tpujob get``
- ``kubectl describe pytorchjob`` → ``tpujob describe NAME`` (spec, status,
  Events — the reference's user-facing observability surface)
- ``kubectl logs``                → ``tpujob logs NAME``
- ``kubectl delete``              → ``tpujob delete NAME``
- operator flags (--namespace, --enable-gang-scheduling, --threadiness,
  --monitoring-port; SURVEY.md §2 "Entrypoint/CLI") → supervisor flags
  (--state-dir, --no-gang, --max-slots, metrics file)

Usage: ``python -m pytorch_operator_tpu.client.cli <command> ...``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from typing import Optional

from ..api import (
    ConditionType,
    ValidationError,
    load_job,
    set_defaults,
    validate,
)
from ..controller.store import (
    JobStore,
    fs_to_key,
    job_key,
    key_to_fs,
    purge_job_artifacts,
)
from ..controller.supervisor import (
    Supervisor,
    default_state_dir,
    job_timeline,
    schedule_to_first_step_latency,
)


def _state_dir(args) -> Path:
    return Path(args.state_dir) if args.state_dir else default_state_dir()


def _resolve_key(args) -> str:
    return f"{args.namespace}/{args.name}"


def _phase_of(job) -> str:
    for ct in (
        ConditionType.SUCCEEDED,
        ConditionType.FAILED,
        ConditionType.SUSPENDED,
        ConditionType.RESTARTING,
        ConditionType.RUNNING,
        ConditionType.CREATED,
    ):
        if job.has_condition(ct):
            return ct.value
    return "Pending"


def _age(ts: Optional[float]) -> str:
    if ts is None:
        return "-"
    s = int(time.time() - ts)
    if s < 120:
        return f"{s}s"
    if s < 7200:
        return f"{s // 60}m"
    return f"{s // 3600}h"


def _load_fault_plan(path):
    """Parse a fault-plan file, or exit with a spec-style error."""
    from pytorch_operator_tpu.faults import FaultPlan

    try:
        return FaultPlan.load(path)
    except (OSError, ValueError, KeyError, TypeError) as e:
        raise SystemExit(f"error: invalid fault plan {path}: {e}")


def _arm_cli_tracing(args) -> None:
    """``--trace``: arm the flight recorder for this process AND every
    replica it spawns — the supervisor's spans land in
    ``<state>/trace/``, each job's in ``<state>/trace/<ns>_<job>/``
    (the reconciler injects the per-job dir whenever process tracing is
    on). ``tpujob trace <job>`` merges them afterward."""
    if not getattr(args, "trace", False):
        return
    import os

    from pytorch_operator_tpu import obs

    os.environ["TPUJOB_TRACE_DIR"] = str(_state_dir(args) / "trace")
    obs.reset_tracer()  # re-read the env this process already cached


def _run_foreground(args, fault_plan=None, chaos: bool = False) -> int:
    """Shared supervise-to-completion loop behind ``run`` and ``chaos``.

    With a fault plan armed, controller-side faults fire in-process and
    worker-side faults ride into replicas via the runner's env
    threading; ``chaos`` additionally prints a timestamp-free replay
    summary — the artifact two runs of one plan+seed must reproduce
    byte-identically (the determinism contract tests pin)."""
    from pytorch_operator_tpu import faults

    job = load_job(args.file)
    if fault_plan is not None:
        # Plan lint: a fault aimed at a replica this spec can never run
        # silently never fires — warn up front (the run still proceeds;
        # the plan may be shared across differently-shaped jobs).
        from pytorch_operator_tpu.faults.plan import validate_against_job

        set_defaults(job)
        for warning in validate_against_job(fault_plan, job):
            print(f"warning: fault plan: {warning}", file=sys.stderr)
        faults.arm(fault_plan)
    _arm_cli_tracing(args)
    sup = Supervisor(
        state_dir=_state_dir(args),
        gang_enabled=not args.no_gang,
        max_slots=args.max_slots,
    )
    try:
        try:
            key = sup.submit(job)
        except ValidationError as e:
            print("error: invalid TPUJob spec:", file=sys.stderr)
            for msg in e.errors:
                print(f"  - {msg}", file=sys.stderr)
            return 2
        print(f"tpujob {key} submitted")
        if fault_plan is not None:
            sup.events.normal(
                key, "ChaosPlanArmed",
                f"fault plan armed: {fault_plan.summary()}",
            )
        printed = 0
        # monotonic: the foreground wait budget must not move with NTP.
        deadline = (
            None if args.timeout is None else time.monotonic() + args.timeout
        )
        while True:
            if fault_plan is not None:
                # The daemon's sync_once runs this hook; the foreground
                # loop syncs one key directly, so drive it here.
                sup._inject_pass_faults()
            # Sync only the submitted job — other persisted jobs in this
            # state dir may be owned by a running daemon.
            sup.reconciler.sync(key)
            events = sup.events.for_job(key)
            for ev in events[printed:]:
                print(f"  [{ev.type}] {ev.reason}: {ev.message}")
            printed = len(events)
            j = sup.get(key)
            if j is None or j.is_finished():
                break
            if deadline is not None and time.monotonic() > deadline:
                print(f"error: timeout after {args.timeout}s", file=sys.stderr)
                sup.delete_job(key)
                return 3
            time.sleep(sup.poll_interval)
        # No settle pass needed: within one sync, runner.sync observes
        # the exit BEFORE the status scan runs, so every record a
        # replica wrote is folded into events by the pass that
        # completes the job.
    finally:
        sup.shutdown()
        if fault_plan is not None:
            faults.disarm()
        if getattr(args, "trace", False):
            from pytorch_operator_tpu import obs

            rec = obs.tracer()
            if rec is not None:
                rec.flush()  # buffered supervisor spans, visible now
    if j is None:
        print("job was garbage-collected")
        return 0
    phase = _phase_of(j)
    lat = schedule_to_first_step_latency(j)
    if lat is not None:
        print(f"schedule-to-first-step latency: {lat:.3f}s")
    print(f"tpujob {key}: {phase} (restarts={j.status.restart_count})")
    if chaos:
        # The deterministic replay artifact: event sequence (no
        # timestamps, no counts), final phase, restart count.
        seq = " -> ".join(f"{ev.type}:{ev.reason}" for ev in events)
        print(f"chaos events: {seq}")
        print(f"chaos final: {phase} restarts={j.status.restart_count}")
    return 0 if j.is_succeeded() else 1


def cmd_run(args) -> int:
    plan = None
    if getattr(args, "fault_plan", None):
        plan = _load_fault_plan(args.fault_plan)
    return _run_foreground(args, fault_plan=plan)


def cmd_chaos(args) -> int:
    """Replay a declared failure scenario end-to-end: arm the plan, run
    the job under it, print the deterministic replay summary. With
    ``--record``, the positional argument is a JOB NAME instead of a
    spec file: reconstruct a replayable plan from that job's recorded
    failure artifacts (faults/record.py) and write it out — a watched
    incident becomes a committed regression test."""
    if getattr(args, "record", False):
        return _cmd_chaos_record(args)
    if not args.plan:
        print("error: --plan is required (or use --record NAME)",
              file=sys.stderr)
        return 2
    return _run_foreground(
        args, fault_plan=_load_fault_plan(args.plan), chaos=True
    )


def _cmd_chaos_record(args) -> int:
    from pytorch_operator_tpu.faults.record import plan_from_recording

    state = _state_dir(args)
    key = f"{args.namespace}/{args.file}"
    plan = plan_from_recording(state, key)
    if not plan.faults:
        print(
            f"error: no replayable failure found in the recording of "
            f"tpujob {key} (no hung-world kill, crash exit, or "
            "checkpoint-save failure on record)",
            file=sys.stderr,
        )
        return 1
    body = json.dumps(plan.to_dict(), indent=2, sort_keys=True) + "\n"
    if args.out:
        Path(args.out).write_text(body)
        print(
            f"wrote {args.out}: {plan.summary()}\n"
            f"replay with: tpujob chaos <job.yaml> --plan {args.out}"
        )
    else:
        print(body, end="")
    return 0


def _load_validated_job(path):
    """Load + default + validate a spec file, or None after printing the
    errors (shared by submit/apply)."""
    job = load_job(path)
    set_defaults(job)
    try:
        validate(job)
    except ValidationError as e:
        print("error: invalid TPUJob spec:", file=sys.stderr)
        for msg in e.errors:
            print(f"  - {msg}", file=sys.stderr)
        return None
    return job


def cmd_submit(args) -> int:
    job = _load_validated_job(args.file)
    if job is None:
        return 2
    store = JobStore(persist_dir=_state_dir(args) / "jobs")
    try:
        key = store.add(job)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(f"tpujob {key} submitted (run 'tpujob supervisor' to reconcile)")
    return 0


def _parse_queue_slots(spec):
    """``'default=4,batch=2'`` → ``{'default': 4, 'batch': 2}``."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        name, _, cap = part.partition("=")
        name = name.strip()
        if not name or not cap:
            raise SystemExit(f"--queue-slots: malformed entry {part!r}")
        try:
            n = int(cap)
        except ValueError:
            raise SystemExit(f"--queue-slots: non-integer cap in {part!r}")
        if n <= 0:
            raise SystemExit(f"--queue-slots: cap must be positive in {part!r}")
        if name in out:
            raise SystemExit(f"--queue-slots: duplicate queue {name!r}")
        out[name] = n
    return out


def cmd_supervisor(args) -> int:
    # SIGTERM (systemd stop / kubelet-style termination) takes the same
    # clean shutdown path as Ctrl-C: kill replicas, release the lease.
    # One-shot: a re-delivered SIGTERM during the cleanup itself must not
    # abort it (that would orphan replicas and hold the lease).
    import signal

    def _sigterm(signum, frame):
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    _arm_cli_tracing(args)
    shards = getattr(args, "shards", None)
    sync_workers_max = getattr(args, "sync_workers_max", None)
    if sync_workers_max is None and os.environ.get("TPUJOB_SYNC_WORKERS_MAX"):
        try:
            sync_workers_max = int(os.environ["TPUJOB_SYNC_WORKERS_MAX"])
        except ValueError:
            pass
    sup = Supervisor(
        state_dir=_state_dir(args),
        gang_enabled=not args.no_gang,
        max_slots=args.max_slots,
        # Sharding replaces leader election: N ACTIVE reconcilers, one
        # per shard set, is the whole point.
        leader_elect=not args.no_leader_elect and not shards,
        queue_slots=_parse_queue_slots(getattr(args, "queue_slots", None)),
        preempt=getattr(args, "preempt", False),
        standby=getattr(args, "standby", 0) or 0,
        shards=shards,
        supervisor_id=getattr(args, "supervisor_id", None),
        lease_ttl=getattr(args, "lease_ttl", 5.0),
        sync_workers_max=sync_workers_max,
    )
    if shards:
        print(
            f"tpujob supervisor: sharded control plane — identity "
            f"{sup.identity}, {shards} shards, lease ttl "
            f"{getattr(args, 'lease_ttl', 5.0):g}s"
        )
    # Monitoring comes up BEFORE the lease wait: a standby must answer
    # /healthz while blocked (it reports is_leader=false), or liveness
    # probes would kill the hot spare.
    monitoring = None

    def start_monitoring() -> bool:
        nonlocal monitoring
        from ..controller.monitoring import MonitoringServer, supervisor_health
        from ..obs import top as obs_top

        monitoring = MonitoringServer(
            render_metrics=sup.metrics.render_text,
            health=lambda: supervisor_health(sup),
            port=args.monitoring_port,
            # `curl :port/top` — the tpujob-top table over HTTP;
            # `curl :port/alerts` — the live health engine's state
            # (in-memory: the watch is THE source, no log re-read).
            text_routes={
                "/top": lambda: obs_top.render(sup.state_dir) + "\n",
                "/alerts": lambda: sup.watch.render_text() + "\n",
            },
        )
        try:
            print(f"tpujob supervisor: monitoring on 127.0.0.1:{monitoring.start()}")
            return True
        except OSError as e:
            monitoring = None
            print(
                f"warning: cannot bind monitoring port {args.monitoring_port}: {e}",
                file=sys.stderr,
            )
            return False

    if args.monitoring_port is not None and not start_monitoring():
        # A fixed port is typically held by the current leader on this
        # host. A standby must still reach the lease wait (the hot-spare
        # property), so only a non-HA daemon treats this as fatal.
        if sup.lease is None:
            sup.shutdown()
            return 2
        print("tpujob supervisor: will retry monitoring bind after lease", flush=True)
    try:
        if sup.lease is not None and not sup.lease.acquire(blocking=False):
            holder = sup.lease.holder()
            print(
                f"tpujob supervisor: standby — lease held by {holder}; waiting",
                flush=True,
            )
            sup.lease.acquire()  # blocks until the leader exits or crashes
            print("tpujob supervisor: acquired leader lease", flush=True)
            # Takeover: adopt the worlds the dead leader left running —
            # this runner loaded (empty) records at startup, before the
            # leader launched anything.
            sup.runner.rescan()
        if args.monitoring_port is not None and monitoring is None:
            # The dead leader's exit freed its port; best effort rebind.
            start_monitoring()
        print(f"tpujob supervisor: state dir {sup.state_dir}, "
              f"gang={'on' if not args.no_gang else 'off'}")
        while True:
            try:
                sup.store.rescan()
                sup.process_deletion_markers()
                sup.process_scale_markers()
                sup.process_suspend_markers()
                sup.process_apply_markers()
                sup.sync_once()
                # Retire reconcile locks of deleted jobs (delete_job
                # can't: it may run nested under a held lock).
                sup.reconciler.gc_key_locks(
                    {job_key(j) for j in sup.store.list()}
                )
                sup.write_metrics_file()
            except Exception:
                # Controller semantics (the reference's workqueue requeues
                # on sync error): a transient failure in one pass — disk
                # hiccup, one bad job record — must not crash the daemon,
                # whose shutdown would tear down every live training
                # world it spawned. Log and keep reconciling.
                import traceback

                traceback.print_exc()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        print("supervisor: shutting down")
        return 0
    finally:
        if monitoring is not None:
            monitoring.stop()
        sup.shutdown()


def cmd_get(args) -> int:
    if getattr(args, "watch", False):
        return _get_watch(args)
    return _get_once(args)


def _get_watch(args) -> int:
    """kubectl get -w analog: re-render whenever a watched job's STATE
    changes (poll the persisted store — it IS the watch surface; the
    reconciler writes every transition through it). Change detection
    runs on a state fingerprint, NOT the rendered text: the AGE column
    ticks every second and must not trigger re-renders. ``--json``
    streams bare snapshots with no separator (kubectl -w -o json)."""

    jobs_dir = _state_dir(args) / "jobs"
    mtimes: dict = {}

    def refresh(store) -> None:
        # Read-only observer: the transitions being watched are written
        # by the owning supervisor process, so list()'s in-process cache
        # must be refreshed from disk — but only for files whose mtime
        # actually moved (a flat rescan+reload would parse every job's
        # JSON twice per 0.5s poll forever).
        nonlocal mtimes
        current: dict = {}
        for p in jobs_dir.glob("*.json"):
            try:
                st = p.stat()
                # (mtime_ns, size): on filesystems with coarse mtime
                # granularity two writes can land in one tick, and a
                # final transition written in the same tick as the
                # previous write would otherwise stay invisible forever.
                current[p.name] = (st.st_mtime_ns, st.st_size)
            except OSError:
                pass  # deleted mid-scan
        if current == mtimes:
            return
        store.rescan()  # picks up newly submitted jobs
        for name in set(mtimes) | set(current):
            if mtimes.get(name) != current.get(name):
                store.reload(fs_to_key(name[: -len(".json")]))
        mtimes = current

    def fingerprint(store) -> list:
        refresh(store)
        jobs = store.list()
        if args.name:
            jobs = [
                j for j in jobs
                if j.metadata.name == args.name
                and j.metadata.namespace == args.namespace
            ]
        return sorted(
            (
                job_key(j),
                _phase_of(j),
                j.status.restart_count,
                j.spec.run_policy.scheduling_policy.queue,
                j.spec.run_policy.scheduling_policy.priority,
            )
            for j in jobs
        )

    store = JobStore(persist_dir=_state_dir(args) / "jobs")
    last = None
    try:
        while True:
            fp = fingerprint(store)
            if fp != last:
                if last is not None and not getattr(args, "json", False):
                    print("---")
                rc = _get_once(args, missing_ok=True, store=store)
                if rc != 0:
                    return rc
                sys.stdout.flush()
                last = fp
            time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def _get_once(args, missing_ok: bool = False, store=None) -> int:
    if store is None:
        store = JobStore(persist_dir=_state_dir(args) / "jobs")
    jobs = store.list()
    if args.name:
        jobs = [j for j in jobs if j.metadata.name == args.name
                and j.metadata.namespace == args.namespace]
        if not jobs and not missing_ok:
            print(f"error: tpujob {_resolve_key(args)} not found", file=sys.stderr)
            return 1
    if getattr(args, "json", False):
        # kubectl -o json analog: the full stored objects, parseable.
        out = [j.to_dict() for j in sorted(
            jobs, key=lambda j: j.metadata.creation_timestamp or 0
        )]
        print(json.dumps(out[0] if args.name and len(out) == 1 else out, indent=2))
        return 0
    # QUEUE/PRIORITY columns appear only when some job sets them — the
    # default listing stays as terse as kubectl's.
    show_sched = any(
        j.spec.run_policy.scheduling_policy.queue
        or j.spec.run_policy.scheduling_policy.priority
        for j in jobs
    )
    header = ("NAME", "NAMESPACE", "STATE", "RESTARTS", "AGE")
    if show_sched:
        header += ("QUEUE", "PRIORITY")
    rows = [header]
    for j in sorted(jobs, key=lambda j: j.metadata.creation_timestamp or 0):
        row = (
            j.metadata.name,
            j.metadata.namespace,
            _phase_of(j),
            str(j.status.restart_count),
            _age(j.metadata.creation_timestamp),
        )
        if show_sched:
            sp = j.spec.run_policy.scheduling_policy
            row += (sp.queue or "default", str(sp.priority))
        rows.append(row)
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)))
    return 0


def _render_request_waterfall(doc: dict, rid: str) -> Optional[str]:
    """Clock-aligned text waterfall for ONE request: every serve-path
    span whose args carry this rid (enqueue → claim → dispatch →
    ring/spool transit → slot wait → decode → respond → publish),
    offsets relative to the first hop, a proportional bar per hop, and
    the emitting process named from the trace metadata. None when the
    merged doc has no spans for the rid."""
    pid_names = {
        e.get("pid"): (e.get("args") or {}).get("name", "")
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    hops = [
        e
        for e in doc.get("traceEvents", [])
        if e.get("ph") == "X" and (e.get("args") or {}).get("rid") == rid
    ]
    if not hops:
        return None
    hops.sort(key=lambda e: (e.get("ts", 0), e.get("name", "")))
    t0 = hops[0].get("ts", 0)
    t_end = max(e.get("ts", 0) + e.get("dur", 0) for e in hops)
    total_us = max(t_end - t0, 1)
    width = 32
    corrected = any(
        e.get("ph") == "M" and e.get("name") == "clock_sync_correction"
        for e in doc.get("traceEvents", [])
    )
    lines = [
        f"request {rid} — {len(hops)} hop(s), "
        f"{total_us / 1e3:.3f}ms end to end"
        + (", clock-synced" if corrected else "")
    ]
    for e in hops:
        off = e.get("ts", 0) - t0
        dur = e.get("dur", 0)
        lead = min(int(width * off / total_us), width - 1)
        blen = max(1, min(int(round(width * dur / total_us)), width - lead))
        bar = " " * lead + "#" * blen
        extras = " ".join(
            f"{k}={v}"
            for k, v in sorted((e.get("args") or {}).items())
            if k != "rid"
        )
        who = pid_names.get(e.get("pid"), "") or "?"
        lines.append(
            f"  {off / 1e3:9.3f}ms  {e.get('name', '?'):<13} "
            f"{dur / 1e3:9.3f}ms  |{bar:<{width}}|  {who}"
            + (f"  {extras}" if extras else "")
        )
    return "\n".join(lines)


def cmd_trace(args) -> int:
    """Merge the supervisor's and every replica's span files into one
    Chrome-trace/Perfetto JSON for this job (obs/trace.py), with
    per-replica clock corrections from the heartbeat-matching estimator
    (obs/clock.py) so cross-host timelines come out causally ordered.
    Open the output at https://ui.perfetto.dev or chrome://tracing."""
    from pytorch_operator_tpu.obs import merge_trace_files
    from pytorch_operator_tpu.obs.clock import (
        estimate_job_offsets,
        offsets_for_trace_files,
    )
    from pytorch_operator_tpu.obs.trace import span_files, span_self_times

    state = _state_dir(args)
    key = _resolve_key(args)
    trace_root = state / "trace"
    # Replica spans live in the per-job dir the reconciler injected;
    # supervisor spans (pass phases, per-job reconciles, store I/O)
    # directly under the root. Rotated ring generations included.
    paths = span_files(trace_root / key_to_fs(key)) + span_files(trace_root)
    if not paths:
        print(
            f"error: no span files for tpujob {key} under {trace_root} — "
            "run with --trace or set spec.observability.trace: true",
            file=sys.stderr,
        )
        return 1
    # Clock alignment: per-replica offsets estimated from the job's
    # heartbeat observation log (empty → no corrections, the single-host
    # behavior). --no-clock-sync keeps raw per-host timestamps.
    offsets = {}
    if not getattr(args, "no_clock_sync", False):
        estimates = estimate_job_offsets(state, key)
        offsets = offsets_for_trace_files(paths, estimates)
        for p, off in sorted(offsets.items()):
            print(
                f"clock_sync: {Path(p).name} corrected by {off:+.6f}s",
                file=sys.stderr,
            )
    doc = merge_trace_files(paths, clock_offsets=offsets or None)
    n_spans = sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
    rid = getattr(args, "request", None)
    if rid:
        # Per-request waterfall: the serve-path hop spans for one rid,
        # already on the aligned clock, rendered as text (the full
        # Perfetto doc still lands in --out when asked).
        text = _render_request_waterfall(doc, rid)
        if text is None:
            print(
                f"error: no spans carry request id {rid!r} "
                f"({n_spans} spans searched) — was the request served "
                "with tracing on?",
                file=sys.stderr,
            )
            return 1
        print(text)
        if args.out:
            Path(args.out).write_text(json.dumps(doc) + "\n")
            print(f"\nwrote {args.out}")
        return 0
    # Where the time went, layer by layer: a span's self time is its
    # duration less its children's (the spans naming it as `parent`).
    rows = sorted(
        span_self_times(doc["traceEvents"]).items(),
        key=lambda kv: -kv[1]["self_ms"],
    )
    print("self time by span (ms): name count total self", file=sys.stderr)
    for name, row in rows[:12]:
        print(
            f"  {name:28s} {row['count']:7d} {row['total_ms']:12.3f} "
            f"{row['self_ms']:12.3f}",
            file=sys.stderr,
        )
    if args.out:
        Path(args.out).write_text(json.dumps(doc) + "\n")
        print(
            f"wrote {args.out}: {n_spans} spans from {len(paths)} file(s) "
            "(open in https://ui.perfetto.dev)"
        )
    else:
        print(json.dumps(doc))
    return 0


def cmd_why(args) -> int:
    """The postmortem engine (obs/analyze.py): reconstruct the job's
    causal timeline from recorded artifacts — clock-aligned heartbeats,
    events, spans — and run the detector pass (step-time regression,
    feed-stall dominance, checkpoint lag, heartbeat silence, straggler).
    Strictly offline: reads the state dir, touches no live process."""
    from pytorch_operator_tpu.obs import analyze as obs_analyze

    state = _state_dir(args)
    key = _resolve_key(args)
    report = obs_analyze.analyze(state, key, window_s=args.window)
    if (
        not report["replicas"]
        and not report["events"]
        and report["phase"] is None
    ):
        print(
            f"error: no recorded artifacts for tpujob {key} under {state} "
            "(no status records, events, or job object)",
            file=sys.stderr,
        )
        return 1
    if args.out:
        Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
    if getattr(args, "json", False):
        print(json.dumps(report, indent=2))
    else:
        print(obs_analyze.render_report(report))
        if args.out:
            print(f"\nwrote {args.out}")
    return 0


def _follow_alerts(args, state: Path, key: str) -> int:
    """``alerts --follow``: live-tail one job's alert transition log
    (like ``tpujob events -f``): incremental offset reads, each
    firing/resolved transition printed once, rotation-tolerant (a
    shrunken file restarts from zero). Ends when the job record
    finishes or disappears, after a final drain."""
    from pytorch_operator_tpu.obs.watch import format_alert_record, job_alert_log

    path = job_alert_log(state, key)
    store = JobStore(persist_dir=state / "jobs")
    offset = 0

    def drain() -> None:
        nonlocal offset
        try:
            size = path.stat().st_size
        except OSError:
            return
        if size < offset:
            offset = 0  # rotated under us: replay the fresh generation
        if size == offset:
            return
        try:
            with path.open("rb") as f:
                f.seek(offset)
                chunk = f.read()
        except OSError:
            return
        last_nl = chunk.rfind(b"\n")
        if last_nl < 0:
            return  # torn line: wait for the writer to finish it
        offset += last_nl + 1
        for line in chunk[: last_nl + 1].splitlines():
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "rule" in rec:
                print(format_alert_record(rec), flush=True)

    try:
        while True:
            job = store.reload(key)
            finished = job is None or job.is_finished()
            drain()  # after the finish check: the last pass drains fully
            if finished:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def cmd_alerts(args) -> int:
    """The live health engine's alert surface (obs/watch.py): current
    state per (job, rule, replica) folded from the per-job alert logs —
    file-based, so it answers with or without a daemon. ``--follow``
    live-tails one job's transitions; ``--json`` emits the raw
    records."""
    from pytorch_operator_tpu.obs import watch as obs_watch

    state = _state_dir(args)
    if getattr(args, "follow", False):
        if not args.name:
            print("error: --follow requires a job NAME", file=sys.stderr)
            return 2
        return _follow_alerts(args, state, _resolve_key(args))
    key = _resolve_key(args) if args.name else None
    if getattr(args, "json", False):
        keys = [key] if key else obs_watch.list_alert_jobs(state)
        records = [
            rec for k in keys for rec in obs_watch.load_alert_log(state, k)
        ]
        records.sort(key=lambda r: float(r.get("ts", 0.0)))
        print(json.dumps(records, indent=2))
        return 0
    rows = obs_watch.gather_alert_rows(state, key)
    print(obs_watch.render_alert_table(rows))
    return 0


def _follow_remediations(args, state: Path, key: str) -> int:
    """``remediations --follow``: live-tail one job's remediation audit
    log (same discipline as ``alerts -f``): incremental offset reads,
    each alert→decision→action record printed once, rotation-tolerant
    (a shrunken file restarts from zero). Ends when the job record
    finishes or disappears, after a final drain."""
    from pytorch_operator_tpu.controller.remediation import (
        format_remediation_record,
        job_remediation_log,
    )

    path = job_remediation_log(state, key)
    store = JobStore(persist_dir=state / "jobs")
    offset = 0

    def drain() -> None:
        nonlocal offset
        try:
            size = path.stat().st_size
        except OSError:
            return
        if size < offset:
            offset = 0  # rotated under us: replay the fresh generation
        if size == offset:
            return
        try:
            with path.open("rb") as f:
                f.seek(offset)
                chunk = f.read()
        except OSError:
            return
        last_nl = chunk.rfind(b"\n")
        if last_nl < 0:
            return  # torn line: wait for the writer to finish it
        offset += last_nl + 1
        for line in chunk[: last_nl + 1].splitlines():
            if not line.strip():
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if isinstance(rec, dict) and "action" in rec:
                print(format_remediation_record(rec), flush=True)

    try:
        while True:
            job = store.reload(key)
            finished = job is None or job.is_finished()
            drain()  # after the finish check: the last pass drains fully
            if finished:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def cmd_remediations(args) -> int:
    """The remediation engine's audit surface
    (controller/remediation.py): every alert→decision→action→outcome
    the closed loop recorded, folded from the per-job audit logs —
    file-based, so it answers with or without a daemon. ``--follow``
    live-tails one job's actions; ``--json`` emits the raw records."""
    from pytorch_operator_tpu.controller import remediation as rem

    state = _state_dir(args)
    if getattr(args, "follow", False):
        if not args.name:
            print("error: --follow requires a job NAME", file=sys.stderr)
            return 2
        return _follow_remediations(args, state, _resolve_key(args))
    key = _resolve_key(args) if args.name else None
    keys = [key] if key else rem.list_remediation_jobs(state)
    records = [r for k in keys for r in rem.load_remediation_log(state, k)]
    records.sort(key=lambda r: float(r.get("ts", 0.0)))
    if getattr(args, "json", False):
        print(json.dumps(records, indent=2))
        return 0
    if not records:
        print("no remediation actions recorded.")
        return 0
    for rec in records:
        print(rem.format_remediation_record(rec))
    return 0


def cmd_top(args) -> int:
    """Live one-screen fleet table (obs/top.py): per-job step, steps/s,
    p50/p99 step time, checkpoint lag, feed stall — from the status-dir
    heartbeats plus the daemon's metrics.prom when present.

    On a TTY the repaint loop takes keys (still no curses): ``s`` cycles
    the sort column, ``r`` flips direction, ``/`` starts a job-name
    substring filter (Enter/Esc ends it), ``c`` clears the filter,
    ``q`` quits."""
    from pytorch_operator_tpu.obs import top as obs_top

    state = _state_dir(args)
    if args.once:
        print(obs_top.render(state))
        return 0

    if getattr(args, "diff", False):
        # Delta mode: print the full table once, then only what CHANGED
        # each interval (step-rate moves, new firing alerts, jobs
        # appearing/finishing) — a scrolling incident log instead of a
        # repaint, so nothing scrolls away unseen.
        prev = None
        try:
            while True:
                rows = obs_top.gather_rows(state)
                if prev is None:
                    print(obs_top.render_table(rows))
                else:
                    for line in obs_top.diff_rows(prev, rows):
                        print(line)
                sys.stdout.flush()
                prev = rows
                time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0

    sort_idx = None  # index into obs_top.COLUMNS; None = default order
    reverse = True
    filt = ""
    filter_mode = False

    def paint(interactive: bool) -> None:
        key = None if sort_idx is None else obs_top.COLUMNS[sort_idx][1]
        body = obs_top.render(
            state, sort_key=key, reverse=reverse, filter_str=filt or None,
            color=interactive,  # firing-alert rows highlight on a TTY
        )
        if interactive:
            hint = (
                f"filter> {filt}▏  (Enter=apply, Esc=cancel)"
                if filter_mode
                else "keys: s=sort col  r=reverse  /=filter  c=clear  q=quit"
            )
            body += "\n\n" + hint
        # ANSI clear + home — a poor man's curses, dependency-free.
        sys.stdout.write("\x1b[2J\x1b[H" + body + "\n")
        sys.stdout.flush()

    interactive = sys.stdin.isatty()
    if not interactive:
        # Piped/headless: the plain repaint loop (previous behavior).
        try:
            while True:
                paint(False)
                time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0

    import os
    import select
    import termios
    import tty

    fd = sys.stdin.fileno()
    saved = termios.tcgetattr(fd)
    try:
        tty.setcbreak(fd)
        deadline = 0.0
        while True:
            # monotonic: repaint pacing is pure interval math; an NTP
            # step would freeze or spin the TUI.
            now = time.monotonic()
            if now >= deadline:
                paint(True)
                deadline = now + args.interval
            ready, _, _ = select.select([sys.stdin], [], [], deadline - now)
            if not ready:
                continue
            ch = os.read(fd, 1).decode(errors="replace")
            if filter_mode:
                if ch in ("\r", "\n"):
                    filter_mode = False
                elif ch == "\x1b":  # Esc cancels the filter being typed
                    filter_mode, filt = False, ""
                elif ch in ("\x7f", "\b"):
                    filt = filt[:-1]
                elif ch.isprintable():
                    filt += ch
            elif ch == "q":
                sys.stdout.write("\n")
                return 0
            elif ch == "s":
                sort_idx = 0 if sort_idx is None else sort_idx + 1
                if sort_idx >= len(obs_top.COLUMNS):
                    sort_idx = None
            elif ch == "r":
                reverse = not reverse
            elif ch == "/":
                filter_mode, filt = True, ""
            elif ch == "c":
                filt = ""
            deadline = 0.0  # immediate repaint on any key
    except KeyboardInterrupt:
        return 0
    finally:
        termios.tcsetattr(fd, termios.TCSADRAIN, saved)


def _follow_events(args, state: Path, key: str) -> int:
    """``events --follow``: tail one job's event sink, aggregation-aware
    — the sink appends cumulative-count update records for a repeating
    event, so the follower re-merges the file each poll
    (load_merged_events) and re-prints a record whose count grew
    (crash-loop debugging without re-running describe). Ends when the
    job record finishes or disappears, after a final drain."""
    from pytorch_operator_tpu.controller.events import load_merged_events

    path = state / "events" / (key_to_fs(key) + ".events.jsonl")
    store = JobStore(persist_dir=state / "jobs")
    shown: list = []  # (type, reason, message, count) already printed

    def fmt(rec) -> str:
        count = int(rec.get("count", 1) or 1)
        tail = f" (x{count})" if count > 1 else ""
        return (
            f"[{rec.get('type', '?')}] {rec.get('reason', '?')}: "
            f"{rec.get('message', '')}{tail}"
        )

    def drain() -> None:
        merged = load_merged_events(path)
        for i, rec in enumerate(merged):
            ident = (
                rec.get("type"), rec.get("reason"), rec.get("message"),
                int(rec.get("count", 1) or 1),
            )
            if i < len(shown):
                if shown[i] != ident:
                    # Same position, higher count: the aggregated event
                    # repeated — reprint with the live count.
                    print(fmt(rec), flush=True)
                    shown[i] = ident
            else:
                print(fmt(rec), flush=True)
                shown.append(ident)

    try:
        while True:
            job = store.reload(key)
            finished = job is None or job.is_finished()
            drain()  # after the finish check: the last pass drains fully
            if finished:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def cmd_events(args) -> int:
    """kubectl get events analog: merged per-job event logs, oldest first,
    bounded by --tail. With a NAME, only that job's; ``--follow`` tails
    the job's sink live."""
    from pytorch_operator_tpu.controller.events import load_merged_events

    state = _state_dir(args)
    if getattr(args, "follow", False):
        if not args.name:
            print("error: --follow requires a job NAME", file=sys.stderr)
            return 2
        return _follow_events(args, state, _resolve_key(args))
    ev_dir = _state_dir(args) / "events"
    records = []
    if ev_dir.is_dir():
        for p in sorted(ev_dir.glob("*.events.jsonl")):
            obj = fs_to_key(p.name[: -len(".events.jsonl")])
            if args.name and obj != _resolve_key(args):
                continue
            # A repeating event appends updated records (cumulative
            # count); the loader collapses runs so one crash-loop warning
            # shows once with its live count, not once per flush.
            for rec in load_merged_events(p):
                records.append((float(rec.get("timestamp", 0.0)), obj, rec))
    records.sort(key=lambda r: r[0])
    if args.tail > 0:
        records = records[-args.tail :]
    if not records:
        print("no events")
        return 0
    rows = [("AGE", "TYPE", "OBJECT", "REASON", "MESSAGE")]
    for ts, obj, rec in records:
        count = int(rec.get("count", 1) or 1)
        msg = str(rec.get("message", ""))
        if count > 1:
            msg += f" (x{count})"
        rows.append(
            (
                _age(ts),
                str(rec.get("type", "?")),
                obj,
                str(rec.get("reason", "?")),
                msg,
            )
        )
    widths = [max(len(r[i]) for r in rows) for i in range(4)]
    for r in rows:
        lead = "  ".join(c.ljust(w) for c, w in zip(r[:4], widths))
        print(f"{lead}  {r[4]}")
    return 0


def cmd_describe(args) -> int:
    state = _state_dir(args)
    store = JobStore(persist_dir=state / "jobs")
    key = _resolve_key(args)
    job = store.get(key)
    if job is None:
        print(f"error: tpujob {key} not found", file=sys.stderr)
        return 1
    if getattr(args, "json", False):
        print(json.dumps(job.to_dict(), indent=2))
        return 0
    print(f"Name:       {job.metadata.name}")
    print(f"Namespace:  {job.metadata.namespace}")
    print(f"UID:        {job.metadata.uid}")
    print(f"State:      {_phase_of(job)}")
    print(f"Restarts:   {job.status.restart_count}")
    if job.status.submit_time:
        print(f"Submitted:  {time.ctime(job.status.submit_time)}")
    if job.metadata.labels:
        print("Labels:     " + ", ".join(f"{k}={v}" for k, v in job.metadata.labels.items()))
    if job.metadata.annotations:
        print("Annotations:")
        for k, v in sorted(job.metadata.annotations.items()):
            print(f"  {k}: {v}")
    lat = schedule_to_first_step_latency(job)
    if lat is not None:
        print(f"Schedule-to-first-step: {lat:.3f}s")
    from pytorch_operator_tpu.controller.progress import (
        format_progress,
        job_status_dir,
        read_latest_progress,
    )

    rec = read_latest_progress(job_status_dir(state / "status", key))
    if rec is not None:
        # Live while the job runs; last-known afterward. Read straight
        # from the status files, so it works with or without a daemon.
        print("Training:")
        for line in format_progress(rec, time.time()):
            print(f"  {line}")
    spans = job_timeline(job)
    if spans:
        print("Timeline:")
        for name, seconds in spans:
            print(f"  {name:<28} {seconds:.3f}s")
    sp = job.spec.run_policy.scheduling_policy
    sched = [f"gang={'on' if sp.gang else 'off'}"]
    if sp.min_available is not None:
        sched.append(f"min_available={sp.min_available}")
    if sp.queue:
        sched.append(f"queue={sp.queue}")
    if sp.priority:
        sched.append(f"priority={sp.priority}")
    if job.spec.run_policy.suspend:
        sched.append("SUSPENDED")
    print("Scheduling: " + ", ".join(sched))
    print("Replicas:")
    for rtype, rs in job.spec.replica_specs.items():
        status = job.status.replica_statuses.get(rtype)
        line = f"  {rtype.value}: desired={rs.replicas}"
        if status:
            line += (
                f" active={status.active} succeeded={status.succeeded} "
                f"failed={status.failed}"
            )
        print(line)
    print("Conditions:")
    for c in job.status.conditions:
        print(
            f"  {c.type.value:<12} {str(c.status):<6} {c.reason:<24} {c.message}"
        )
    ev_path = state / "events" / (key_to_fs(key) + ".events.jsonl")
    print("Events:")
    from pytorch_operator_tpu.controller.events import load_merged_events

    merged = load_merged_events(ev_path)
    for ev in merged:
        tail = f" (x{ev['count']})" if int(ev.get("count", 1) or 1) > 1 else ""
        print(f"  [{ev.get('type', '?')}] {ev.get('reason', '?')}: {ev.get('message', '')}{tail}")
    if not merged:
        print("  <none>")
    return 0


def cmd_logs(args) -> int:
    state = _state_dir(args)
    key = _resolve_key(args)
    prefix = key_to_fs(key)
    log_dir = state / "logs"
    if args.replica:
        paths = [log_dir / f"{prefix}-{args.replica}.log"]
        if not paths[0].exists():
            print(f"error: no log for replica {args.replica} of {key}", file=sys.stderr)
            return 1
    else:
        paths = sorted(log_dir.glob(f"{prefix}-*.log"))
        if not paths:
            print(f"error: no logs found for tpujob {key}", file=sys.stderr)
            return 1
    if not args.follow:
        for p in paths:
            if len(paths) > 1:
                print(f"==> {p.name} <==")
            sys.stdout.write(p.read_text(errors="replace"))
        return 0

    # kubectl logs -f analog: one incremental read pass, repeated until the
    # job record is finished OR gone (deleted / TTL-GC'd mid-follow). The
    # finished check runs BEFORE the pass so the last pass drains output
    # written right up to the finish. New replicas appearing mid-follow
    # (restarts) are picked up by the glob.
    store = JobStore(persist_dir=state / "jobs")
    offsets: dict = {}

    def read_pass() -> None:
        for p in sorted(log_dir.glob(f"{prefix}-*.log")):
            if args.replica and not p.name.endswith(f"-{args.replica}.log"):
                continue
            off = offsets.get(p, 0)
            try:
                with p.open("rb") as f:
                    f.seek(off)
                    data = f.read()
            except OSError:
                continue  # purged under us — nothing more to print
            if data:
                sys.stdout.write(data.decode(errors="replace"))
                sys.stdout.flush()
                offsets[p] = off + len(data)

    try:
        while True:
            job = store.reload(key)
            finished = job is None or job.is_finished()
            read_pass()
            if finished:
                return 0
            time.sleep(0.5)
    except KeyboardInterrupt:
        return 0


def cmd_delete(args) -> int:
    state = _state_dir(args)
    key = _resolve_key(args)
    store = JobStore(persist_dir=state / "jobs")
    job = store.get(key)
    if job is None:
        print(f"error: tpujob {key} not found", file=sys.stderr)
        return 1
    # Cross-process delete: leave a marker a running supervisor will act on
    # (it owns the replica processes); also remove the stored object so the
    # job disappears from get/describe immediately.
    # The marker carries the purge request: a running supervisor purges
    # AFTER killing the replicas (else a live workload's next checkpoint
    # save would re-create the dir behind the purge). The immediate purge
    # below covers the daemon-less case (no replicas running).
    store.mark_deletion(key, purge=args.purge, uid=job.metadata.uid or "")
    store.delete(key)
    if args.purge:
        purge_job_artifacts(state, key)
    print(f"tpujob {key} deleted")
    return 0


def cmd_scale(args) -> int:
    """Elastic resize: validate against the stored spec, then leave a marker
    for the owning supervisor (it must re-rendezvous the live gang)."""
    state = _state_dir(args)
    key = _resolve_key(args)
    store = JobStore(persist_dir=state / "jobs")
    job = store.get(key)
    if job is None:
        print(f"error: tpujob {key} not found", file=sys.stderr)
        return 1
    ep = job.spec.elastic_policy
    if ep is None:
        print(f"error: tpujob {key} has no elastic_policy", file=sys.stderr)
        return 2
    if not (ep.min_replicas <= args.workers <= ep.max_replicas):
        print(
            f"error: workers={args.workers} outside "
            f"[{ep.min_replicas}, {ep.max_replicas}]",
            file=sys.stderr,
        )
        return 2
    store.mark_scale(key, args.workers)
    print(f"tpujob {key} scale to {args.workers} workers requested")
    return 0


def cmd_apply(args) -> int:
    """kubectl apply analog: create or update. A new job is stored
    directly; an update to an existing job is left as a marker for the
    owning supervisor (it may need to restart the world at the new
    shape)."""
    from ..controller.store import job_key as _job_key

    job = _load_validated_job(args.file)
    if job is None:
        return 2
    store = JobStore(persist_dir=_state_dir(args) / "jobs")
    key = _job_key(job)
    if store.get(key) is None:
        try:
            store.add(job)
        except ValueError:
            # Lost a create race with a concurrent apply — fall through to
            # the update path.
            store.mark_apply(key, job.to_dict())
            print(f"tpujob {key} update requested")
            return 0
        print(f"tpujob {key} created (run 'tpujob supervisor' to reconcile)")
    else:
        store.mark_apply(key, job.to_dict())
        print(f"tpujob {key} update requested")
    return 0


def _cmd_set_suspend(args, flag: bool) -> int:
    """Suspend/resume: leave a marker for the owning supervisor (it owns
    the replica processes, so it performs the teardown/relaunch)."""
    state = _state_dir(args)
    key = _resolve_key(args)
    store = JobStore(persist_dir=state / "jobs")
    job = store.get(key)
    if job is None:
        print(f"error: tpujob {key} not found", file=sys.stderr)
        return 1
    if job.is_finished():
        print(f"error: tpujob {key} already finished", file=sys.stderr)
        return 2
    store.mark_suspend(key, flag)
    print(f"tpujob {key} {'suspend' if flag else 'resume'} requested")
    return 0


def cmd_suspend(args) -> int:
    return _cmd_set_suspend(args, True)


def cmd_resume(args) -> int:
    return _cmd_set_suspend(args, False)


def cmd_metrics(args) -> int:
    # Unsharded daemons write metrics.prom; sharded ones write one
    # metrics-<identity>.prom each — print the union.
    paths = sorted(_state_dir(args).glob("metrics*.prom"))
    if not paths:
        print("no metrics recorded yet", file=sys.stderr)
        return 1
    for path in paths:
        if len(paths) > 1:
            sys.stdout.write(f"# ---- {path.name} ----\n")
        sys.stdout.write(path.read_text())
    return 0


def cmd_serve_request(args) -> int:
    """Submit a request to a serving job's spool and (optionally) wait
    for the response — the client half of the serving service
    (serving/spool.py; the serve workload is the engine half).

    ``--job`` targets a ``spec.serving`` job's FRONT spool (resolved
    from the supervisor state layout — the router fans the request out
    across replicas); ``--spool`` names a spool directory directly
    (single-engine serve jobs that picked their own path)."""
    from pathlib import Path

    from pytorch_operator_tpu.serving import Spool

    if (args.prompt is None) == (args.prompt_len is None):
        print(
            "exactly one of --prompt / --prompt-len is required",
            file=sys.stderr,
        )
        return 2
    if (args.spool is None) == (args.job is None):
        print(
            "exactly one of --spool / --job is required",
            file=sys.stderr,
        )
        return 2
    if args.job is not None:
        from pytorch_operator_tpu.controller.store import JobStore
        from pytorch_operator_tpu.serving.router import (
            front_spool_dir,
            serve_root_dir,
        )

        state = _state_dir(args)
        key = (
            args.job
            if "/" in args.job
            else f"{args.namespace}/{args.job}"
        )
        job = JobStore(persist_dir=state / "jobs").get(key)
        if job is None:
            print(f"error: tpujob {key} not found", file=sys.stderr)
            return 1
        if job.spec.serving is None:
            print(
                f"error: tpujob {key} has no spec.serving block — not a "
                "serving job (use --spool for raw spools)",
                file=sys.stderr,
            )
            return 2
        args.spool = str(
            front_spool_dir(serve_root_dir(state), key, job.spec.serving)
        )
    prompt = None
    if args.prompt is not None:
        try:
            prompt = [int(t) for t in args.prompt.split(",") if t.strip()]
        except ValueError:
            print(
                f"--prompt must be comma-separated token ids, got "
                f"{args.prompt!r}",
                file=sys.stderr,
            )
            return 2
        if not prompt:
            print(
                f"--prompt contains no token ids: {args.prompt!r}",
                file=sys.stderr,
            )
            return 2
    # The SERVE JOB owns spool creation; the client creating a fresh
    # spool at a typo'd path would leave dead directories and block the
    # full timeout on a request nothing will ever read.
    if not Path(args.spool).is_dir():
        print(
            f"spool {args.spool!r} does not exist — is the serve job "
            "running? (its --spool flag names the directory)",
            file=sys.stderr,
        )
        return 1
    spool = Spool(args.spool)
    rid = spool.submit(
        prompt=prompt,
        prompt_len=args.prompt_len,
        max_new_tokens=args.max_new_tokens,
    )
    if args.no_wait:
        print(rid)
        return 0
    try:
        resp = spool.wait_response(rid, timeout=args.timeout)
    except TimeoutError as e:
        print(str(e), file=sys.stderr)
        return 1
    print(json.dumps(resp))
    return 0 if "error" not in resp else 1


def cmd_bench_control_plane(args) -> int:
    """Control-plane benchmark: supervisor pass latency + store I/O for N
    synthetic jobs, cached vs legacy store plus multi-supervisor sharded
    cells (workloads/ctrlplane_bench)."""
    from pytorch_operator_tpu.workloads import ctrlplane_bench

    argv = ["--jobs", args.jobs, "--passes", str(args.passes)]
    for flag, value in (
        ("--sharded-cells", args.sharded_cells),
        ("--gang-cells", args.gang_cells),
        ("--churn-cells", args.churn_cells),
    ):
        if value is not None:
            argv += [flag, value]
    if args.out:
        argv += ["--out", args.out]
    return ctrlplane_bench.main(argv)


def cmd_bench_data_plane(args) -> int:
    """Data-plane benchmark: checkpoint stall + step throughput across
    {blocking, async, staged} saves x {inline, prefetched} device feeds,
    plus the bursty-producer static-vs-autotuned feed cells
    (workloads/dataplane_bench)."""
    from pytorch_operator_tpu.workloads import dataplane_bench

    argv = [
        "--steps", str(args.steps),
        "--checkpoint-every", str(args.checkpoint_every),
        "--dim", str(args.dim),
        "--feed-steps", str(args.feed_steps),
        "--feed-depth-max", str(args.feed_depth_max),
    ]
    if args.out:
        argv += ["--out", args.out]
    return dataplane_bench.main(argv)


def cmd_bench_serve_plane(args) -> int:
    """Serve-plane benchmark: routed goodput / shed / TTFT across
    replica counts x {healthy, kill_replica, fail_engine_step}, plus
    the zero-router-overhead idle cell (workloads/serveplane_bench)."""
    from pytorch_operator_tpu.workloads import serveplane_bench

    argv = [
        "--replicas", args.replicas,
        "--scenarios", args.scenarios,
        "--rate", str(args.rate),
        "--duration", str(args.duration),
    ]
    if args.smoke:
        argv.append("--smoke")
    if args.out:
        argv += ["--out", args.out]
    return serveplane_bench.main(argv)


def cmd_bench_elastic(args) -> int:
    """Elastic benchmark: resize-in-place vs whole-world-restart recovery
    across real subprocess gangs (workloads/elastic_bench)."""
    from pytorch_operator_tpu.workloads import elastic_bench

    argv = [
        "--gangs", args.gangs,
        "--pre-steps", str(args.pre_steps),
        "--step-time", str(args.step_time),
        "--timeout", str(args.timeout),
    ]
    if args.out:
        argv += ["--out", args.out]
    return elastic_bench.main(argv)


def cmd_manifests(args) -> int:
    # Deploy-manifest generation (SURVEY.md §1 layer 6): the CRD schema is
    # introspected from api/types.py so it cannot drift (api/crdgen.py).
    from pytorch_operator_tpu.api import crdgen

    argv = []
    if args.out_dir:
        argv += ["--out-dir", args.out_dir]
    if args.check:
        argv.append("--check")
    return crdgen.main(argv)


def cmd_verify_invariants(args) -> int:
    """Static invariant checker (analysis/): AST rules over the package,
    gated on zero unsuppressed findings. Tier-1 runs this via
    tests/test_static_analysis.py; the CLI verb is for operators and
    pre-commit use."""
    from pytorch_operator_tpu import analysis

    pkg_root = Path(analysis.__file__).resolve().parent.parent
    root = Path(args.root).resolve() if args.root else pkg_root
    baseline = (
        Path(args.baseline)
        if args.baseline
        else root / "analysis" / "baseline.json"
    )
    try:
        report = analysis.run_verify(root, baseline)
    except analysis.BaselineError as e:
        print(f"verify-invariants: {e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        bl = analysis.Baseline.from_findings(
            report.unsuppressed, justification="TODO: justify or fix"
        )
        bl.save(baseline)
        print(
            f"wrote {len(bl.entries)} entries to {baseline} — edit every "
            "justification before committing",
            file=sys.stderr,
        )
        return 0
    if args.json:
        print(report.to_json())
    else:
        print(report.render_text())
    return report.exit_code()


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tpujob", description="TPU-native distributed training jobs"
    )
    p.add_argument("--state-dir", default=None, help="supervisor state directory")
    sub = p.add_subparsers(dest="command", required=True)

    def add_ns(sp):
        sp.add_argument("-n", "--namespace", default="default")

    sp = sub.add_parser("run", help="submit a job and supervise to completion")
    sp.add_argument("file")
    sp.add_argument("--timeout", type=float, default=None)
    sp.add_argument("--no-gang", action="store_true", help="disable gang scheduling")
    sp.add_argument(
        "--max-slots", type=int, default=None,
        help="device-slot capacity (a replica requesting N chips/devices "
        "occupies N slots)",
    )
    sp.add_argument(
        "--fault-plan", default=None,
        help="arm a deterministic fault plan (YAML/JSON, faults/) for "
        "this run — failures fire in the supervisor and ride into "
        "replicas via TPUJOB_FAULT_PLAN",
    )
    sp.add_argument(
        "--trace", action="store_true",
        help="record flight-recorder spans (supervisor + every replica) "
        "under <state>/trace/ for `tpujob trace`",
    )
    sp.set_defaults(func=cmd_run)

    sp = sub.add_parser(
        "chaos",
        help="replay a declared failure scenario: run a job under a "
        "fault plan and print the deterministic event-sequence summary; "
        "--record NAME instead reconstructs a plan from a recorded "
        "live failure",
    )
    sp.add_argument(
        "file",
        help="TPUJob spec to run under faults (with --record: the job "
        "NAME whose recorded failure to capture)",
    )
    sp.add_argument(
        "--plan", default=None, help="fault plan file (YAML/JSON)"
    )
    sp.add_argument(
        "--record", action="store_true",
        help="capture the named job's recorded failure timeline as a "
        "replayable fault plan instead of running anything",
    )
    sp.add_argument(
        "--out", default=None,
        help="with --record: write the plan JSON here (default: stdout)",
    )
    sp.add_argument("-n", "--namespace", default="default")
    sp.add_argument("--timeout", type=float, default=None)
    sp.add_argument("--no-gang", action="store_true")
    sp.add_argument("--max-slots", type=int, default=None)
    sp.add_argument(
        "--trace", action="store_true",
        help="record flight-recorder spans during the chaos run "
        "(`tpujob trace` shows the failure timeline)",
    )
    sp.set_defaults(func=cmd_chaos)

    sp = sub.add_parser("submit", help="queue a job for a running supervisor")
    sp.add_argument("file")
    sp.set_defaults(func=cmd_submit)

    sp = sub.add_parser("supervisor", help="run the reconcile daemon")
    sp.add_argument("--interval", type=float, default=0.2)
    sp.add_argument("--no-gang", action="store_true")
    sp.add_argument(
        "--max-slots", type=int, default=None,
        help="device-slot capacity (a replica requesting N chips/devices "
        "occupies N slots)",
    )
    sp.add_argument(
        "--queue-slots",
        default=None,
        dest="queue_slots",
        help="per-queue DEVICE-slot caps, e.g. 'default=4,batch=2' — a "
        "replica requesting N chips/devices occupies N of them (jobs "
        "pick a queue via scheduling_policy.queue; unlisted queues are "
        "unbounded)",
    )
    sp.add_argument(
        "--preempt",
        action="store_true",
        help="allow a held high-priority gang to evict lower-priority "
        "running worlds (they relaunch when capacity frees; their "
        "restart budget is untouched)",
    )
    sp.add_argument(
        "--monitoring-port",
        type=int,
        default=None,
        help="serve /metrics and /healthz on this port (0 = auto)",
    )
    sp.add_argument(
        "--no-leader-elect",
        action="store_true",
        help="skip the leader lease (single-daemon setups)",
    )
    sp.add_argument(
        "--shards",
        type=int,
        default=None,
        help="shard the job space N ways across multiple supervisors "
        "sharing this state dir (per-shard store leases with fencing "
        "tokens; every supervisor must pass the same N). Replaces "
        "leader election: each daemon reconciles only the shards it "
        "holds, and shards rebalance within one lease TTL on "
        "join/death/drain",
    )
    sp.add_argument(
        "--supervisor-id",
        default=None,
        help="identity for shard leases and per-supervisor metrics "
        "(default: <hostname>-<pid>)",
    )
    sp.add_argument(
        "--lease-ttl",
        type=float,
        default=5.0,
        help="shard-lease TTL in seconds: the failover bound — an "
        "orphaned shard is re-claimed within one TTL (default 5)",
    )
    sp.add_argument(
        "--sync-workers-max",
        type=int,
        default=None,
        help="ceiling for the latency-driven steady-pool autoscaler "
        "(grows the reconcile pool when the measured steady-phase "
        "latency climbs, shrinks to the floor on an idle fleet; "
        "default min(8, ncpu); env TPUJOB_SYNC_WORKERS_MAX)",
    )
    sp.add_argument(
        "--standby",
        type=int,
        default=0,
        help="keep N pre-warmed standby processes (interpreter + jax "
        "imports already paid) and hand module-template replicas to "
        "them — cuts schedule-to-first-step latency (0 = off)",
    )
    sp.add_argument(
        "--trace", action="store_true",
        help="record flight-recorder spans (supervisor + every replica) "
        "under <state>/trace/ for `tpujob trace`",
    )
    sp.set_defaults(func=cmd_supervisor)

    sp = sub.add_parser("get", help="list jobs")
    sp.add_argument("name", nargs="?")
    sp.add_argument(
        "--json", action="store_true",
        help="full job objects as JSON (kubectl -o json analog)",
    )
    sp.add_argument(
        "-w", "--watch", action="store_true",
        help="keep watching; re-print the table on any state change",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_get)

    sp = sub.add_parser("describe", help="show job details and events")
    sp.add_argument("name")
    sp.add_argument(
        "--json", action="store_true",
        help="the full job object as JSON (kubectl -o json analog)",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_describe)

    sp = sub.add_parser("logs", help="print replica logs")
    sp.add_argument("name")
    sp.add_argument("--replica", default=None, help="e.g. master-0, worker-1")
    sp.add_argument(
        "-f", "--follow", action="store_true",
        help="stream new log output until the job finishes",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_logs)

    sp = sub.add_parser("delete", help="delete a job")
    sp.add_argument("name")
    sp.add_argument(
        "--purge",
        action="store_true",
        help="also remove the job's checkpoint/status artifacts",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_delete)

    sp = sub.add_parser("scale", help="elastic resize of a job's workers")
    sp.add_argument("name")
    sp.add_argument("--workers", type=int, required=True)
    add_ns(sp)
    sp.set_defaults(func=cmd_scale)

    sp = sub.add_parser(
        "events", help="merged event log across jobs (kubectl get events)"
    )
    sp.add_argument(
        "name", nargs="?", default=None,
        help="only this job's events (required with --follow)",
    )
    sp.add_argument(
        "--tail", type=int, default=50, help="show the last N events (0 = all)"
    )
    sp.add_argument(
        "-f", "--follow", action="store_true",
        help="tail the job's event sink live (aggregation-aware: a "
        "crash-looping event re-prints with its growing count) until "
        "the job finishes",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_events)

    sp = sub.add_parser(
        "trace",
        help="merge a job's flight-recorder span files into one "
        "Chrome-trace/Perfetto JSON (record with run/supervisor "
        "--trace or spec.observability.trace)",
    )
    sp.add_argument("name")
    sp.add_argument(
        "--out", default=None,
        help="write the trace JSON here (default: stdout)",
    )
    sp.add_argument(
        "--no-clock-sync", action="store_true", dest="no_clock_sync",
        help="skip the heartbeat-matched per-replica clock corrections "
        "(keep each host's raw timestamps)",
    )
    sp.add_argument(
        "--request", default=None, metavar="RID",
        help="render a clock-aligned text waterfall for one serve "
        "request (enqueue → claim → dispatch → transit → slot wait → "
        "decode → respond) instead of the full trace JSON",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_trace)

    sp = sub.add_parser(
        "why",
        help="postmortem a job from its recorded artifacts: clock-align "
        "the cross-host timeline, run the anomaly detectors (step-time "
        "regression, feed stall, checkpoint lag, heartbeat silence, "
        "straggler), print findings with evidence",
    )
    sp.add_argument("name")
    sp.add_argument(
        "--window", type=float, default=None,
        help="analyze only the last N seconds of the recorded timeline "
        "(default: everything; the regression baseline is what precedes "
        "the window)",
    )
    sp.add_argument(
        "--out", default=None,
        help="also write the machine-readable JSON report here",
    )
    sp.add_argument(
        "--json", action="store_true",
        help="print the JSON report instead of the terminal rendering",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_why)

    sp = sub.add_parser(
        "top",
        help="live fleet table: per-job step, steps/s, p50/p99 step "
        "time, checkpoint lag, feed stall, firing alerts",
    )
    sp.add_argument(
        "--once", action="store_true",
        help="print one snapshot and exit (default: refresh loop)",
    )
    sp.add_argument(
        "--interval", type=float, default=2.0,
        help="refresh interval in seconds",
    )
    sp.add_argument(
        "--diff", action="store_true",
        help="print only deltas vs the previous repaint (step-rate "
        "moves, new firing alerts, jobs appearing/finishing) as a "
        "scrolling log instead of repainting the table",
    )
    sp.set_defaults(func=cmd_top)

    sp = sub.add_parser(
        "alerts",
        help="live health-engine alerts (streaming detector rules + "
        "lifecycle): current state per job/rule/replica from the "
        "per-job alert logs",
    )
    sp.add_argument(
        "name", nargs="?", default=None,
        help="only this job's alerts (required with --follow)",
    )
    sp.add_argument(
        "-f", "--follow", action="store_true",
        help="live-tail the job's alert transitions (firing/resolved) "
        "until the job finishes",
    )
    sp.add_argument(
        "--json", action="store_true",
        help="print the raw transition records as JSON",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_alerts)

    sp = sub.add_parser(
        "remediations",
        help="remediation audit trail: every alert→decision→action→"
        "outcome the closed loop recorded, from the per-job audit logs",
    )
    sp.add_argument(
        "name", nargs="?", default=None,
        help="only this job's remediations (required with --follow)",
    )
    sp.add_argument(
        "-f", "--follow", action="store_true",
        help="live-tail the job's remediation actions until the job "
        "finishes",
    )
    sp.add_argument(
        "--json", action="store_true",
        help="print the raw audit records as JSON",
    )
    add_ns(sp)
    sp.set_defaults(func=cmd_remediations)

    sp = sub.add_parser(
        "apply", help="create or update a job from a spec file (kubectl apply)"
    )
    sp.add_argument("file")
    sp.set_defaults(func=cmd_apply)

    sp = sub.add_parser(
        "suspend", help="suspend a job (tear down replicas, keep the job)"
    )
    sp.add_argument("name")
    add_ns(sp)
    sp.set_defaults(func=cmd_suspend)

    sp = sub.add_parser("resume", help="resume a suspended job")
    sp.add_argument("name")
    add_ns(sp)
    sp.set_defaults(func=cmd_resume)

    sp = sub.add_parser(
        "manifests", help="generate deploy manifests (CRD/RBAC/Deployment)"
    )
    sp.add_argument("--out-dir", default=None, help="default: repo manifests/")
    sp.add_argument("--check", action="store_true", help="verify no drift")
    sp.set_defaults(func=cmd_manifests)

    sp = sub.add_parser("metrics", help="print supervisor metrics")
    sp.set_defaults(func=cmd_metrics)

    sp = sub.add_parser(
        "bench-control-plane",
        help="measure supervisor pass latency + store I/O for N synthetic "
        "jobs (cached vs legacy store); emits a JSON artifact",
    )
    sp.add_argument(
        "--jobs", default="10,100,1000",
        help="comma-separated fleet sizes (default: 10,100,1000)",
    )
    sp.add_argument(
        "--passes", type=int, default=30, help="idle passes per cell"
    )
    sp.add_argument(
        "--sharded-cells", default=None,
        help="multi-supervisor cells as N:S (jobs:supervisors), e.g. "
        "'10000:2,10000:4' (default: 10000:1,10000:2,10000:4; '' "
        "disables)",
    )
    sp.add_argument(
        "--gang-cells", default=None,
        help="wide-gang cells as NxM:S, e.g. '500x16:2' ('' disables)",
    )
    sp.add_argument(
        "--churn-cells", default=None,
        help="marker-heavy churn cells as N:S, e.g. '2000:2' ('' "
        "disables)",
    )
    sp.add_argument(
        "--out", default=None,
        help="write the full artifact to this file",
    )
    sp.set_defaults(func=cmd_bench_control_plane)

    sp = sub.add_parser(
        "bench-data-plane",
        help="measure training-step checkpoint stalls + device-feed "
        "overlap ({blocking, async, staged} saves x {inline, prefetched} "
        "feeds, bursty static-vs-autotuned feed cells); emits a JSON "
        "artifact",
    )
    sp.add_argument("--steps", type=int, default=40, help="timed steps/cell")
    sp.add_argument(
        "--checkpoint-every", type=int, default=5, help="save cadence"
    )
    sp.add_argument(
        "--dim", type=int, default=256,
        help="bench model width (state bytes ~ 96*dim^2)",
    )
    sp.add_argument(
        "--feed-steps", type=int, default=60,
        help="fenced steps per bursty feed cell",
    )
    sp.add_argument(
        "--feed-depth-max", type=int, default=8,
        help="depth budget the autotuned feed cell may grow into",
    )
    sp.add_argument(
        "--out", default=None,
        help="write the full artifact to this file",
    )
    sp.set_defaults(func=cmd_bench_data_plane)

    sp = sub.add_parser(
        "bench-serve-plane",
        help="measure routed serving goodput/shed/TTFT across replica "
        "counts x {healthy, kill_replica, fail_engine_step} plus the "
        "zero-router-overhead idle cell; emits a JSON artifact",
    )
    sp.add_argument(
        "--replicas", default="1,2,4",
        help="comma-separated replica counts per scenario",
    )
    sp.add_argument(
        "--scenarios", default="healthy,kill_replica,fail_engine_step",
    )
    sp.add_argument(
        "--rate", type=float, default=85.0,
        help="offered load, requests/s (open-loop Poisson)",
    )
    sp.add_argument(
        "--duration", type=float, default=6.0,
        help="arrival window per cell, seconds",
    )
    sp.add_argument(
        "--smoke", action="store_true",
        help="tiny under-capacity cells — seconds, not minutes",
    )
    sp.add_argument(
        "--out", default=None,
        help="write the full artifact to this file",
    )
    sp.set_defaults(func=cmd_bench_serve_plane)

    sp = sub.add_parser(
        "bench-elastic",
        help="measure resize-in-place vs whole-world-restart recovery "
        "(kill one worker of a real subprocess gang; wall-clock to the "
        "slowest member's first post-recovery step, step loss, rank "
        "audit); emits a JSON artifact",
    )
    sp.add_argument(
        "--gangs", default="2,4,8",
        help="comma-separated WORKER counts per gang (each gang also "
        "has one master)",
    )
    sp.add_argument(
        "--pre-steps", type=int, default=5,
        help="steps every member must reach before the kill",
    )
    sp.add_argument(
        "--step-time", type=float, default=0.02,
        help="per-step sleep of the bench workload, seconds",
    )
    sp.add_argument(
        "--timeout", type=float, default=120.0,
        help="per-phase (warm-up / recovery) timeout, seconds",
    )
    sp.add_argument(
        "--out", default=None,
        help="write the full artifact to this file",
    )
    sp.set_defaults(func=cmd_bench_elastic)

    sp = sub.add_parser(
        "verify-invariants",
        help="run the static invariant checker (atomic-state-write, "
        "fenced-store-write, lock-order, swallowed-exception, "
        "retry-discipline, clock-discipline, remediation-discipline, "
        "layer-direction) over the package; exit 1 "
        "on any unsuppressed finding",
    )
    sp.add_argument(
        "--json", action="store_true", help="machine-readable report"
    )
    sp.add_argument(
        "--baseline", default=None,
        help="baseline file of accepted findings "
        "(default: <root>/analysis/baseline.json)",
    )
    sp.add_argument(
        "--root", default=None,
        help="package root to analyze (default: the installed "
        "pytorch_operator_tpu package)",
    )
    sp.add_argument(
        "--write-baseline", action="store_true",
        help="accept every current unsuppressed finding into the "
        "baseline (justifications must then be edited by hand)",
    )
    sp.set_defaults(func=cmd_verify_invariants)

    sp = sub.add_parser(
        "serve-request",
        help="submit a request to a serving job's spool and print the "
        "response (tokens + TTFT/per-token latency)",
    )
    sp.add_argument(
        "--spool", default=None, help="a serve job's --spool dir directly"
    )
    sp.add_argument(
        "--job", default=None,
        help="a spec.serving job (name or ns/name): submit to its FRONT "
        "spool — the supervisor's router dispatches across replicas",
    )
    add_ns(sp)
    sp.add_argument(
        "--prompt", default=None,
        help="comma-separated token ids (no tokenizer ships here)",
    )
    sp.add_argument(
        "--prompt-len", type=int, default=None,
        help="synthesize a deterministic prompt of this length instead",
    )
    sp.add_argument("--max-new-tokens", type=int, default=64)
    sp.add_argument(
        "--timeout", type=float, default=300.0,
        help="seconds to wait for the response",
    )
    sp.add_argument(
        "--no-wait", action="store_true",
        help="print the request id and exit (poll responses/<id>.json)",
    )
    sp.set_defaults(func=cmd_serve_request)

    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.func(args)


if __name__ == "__main__":
    raise SystemExit(main())
