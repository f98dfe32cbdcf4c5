"""Control-plane benchmark: supervisor pass latency + store I/O at scale.

The reference operator scales because informer caches and a workqueue
keep reconciles off the API server's hot path; this repo's file-backed
analog must prove the same property with numbers. This bench drives N
synthetic jobs (FakeRunner — no TPU, no subprocesses; pure control
plane) through the full submit → run → finish churn, then measures the
steady-state "idle pass" — every job RUNNING, nothing to reconcile —
which is what a daemon supervising a large fleet spends its life doing.

Three harnesses share the artifact:

- ``cached``  — the production single-supervisor path: dirty-tracking
  persistence, one scandir snapshot per pass, steady fast path, the
  latency-driven pool autoscaler.
- ``legacy``  — ``JobStore(cache=False)`` + serial pass: the pre-cache
  behavior (every rescan re-reads every job file, every persist
  rewrites, one glob per marker kind), kept in-tree precisely so this
  comparison stays honest as the code moves.
- ``sharded`` — S supervisors against ONE state dir, job space split by
  per-shard store leases (controller/leases.py), each supervisor
  running the full daemon loop body. Cells extend to wide gangs (N
  jobs × M replicas) and marker-heavy churn, and every cell carries a
  ``double_reconciles`` counter — the number of jobs two live
  supervisors simultaneously ran worlds for, pinned at ZERO.

Each pass runs the daemon loop body (rescan + the four marker scans +
sync_once), so the numbers measure what ``tpujob supervisor`` actually
pays. Emitted artifact (``--out``): per cell, pass-
latency p50/p99 (ms) and per-pass store I/O, autoscaler pool bounds,
churn throughput, and the multi-supervisor flatness acceptance (idle
p50 at N=10000 with 2 supervisors vs the 63 ms N=1000 single-supervisor
baseline the PR-2 artifact pinned).

Usage:
    python -m pytorch_operator_tpu.workloads.ctrlplane_bench \
        [--jobs 10,100,1000] [--passes 30] [--out ctrlplane.json] \
        [--sharded-cells 10000:1,10000:2,10000:4] \
        [--gang-cells 500x16:2] [--churn-cells 2000:2]
    tpujob bench-control-plane ...
"""

from __future__ import annotations

import argparse
import json
import random
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, round(q * (len(xs) - 1))))
    return xs[idx]


def _make_job(i: int, replicas: int = 1):
    """One synthetic job: a Master plus ``replicas - 1`` Workers (the
    wide-gang cells model N jobs × M replicas this way). Every job is
    ARMED with a remediation policy on purpose: an engine that costs
    I/O while nothing fires would show up in the idle pins below."""
    from ..api.types import (
        ObjectMeta,
        ProcessTemplate,
        RemediationPolicy,
        ReplicaSpec,
        ReplicaType,
        RestartPolicy,
        TPUJob,
        TPUJobSpec,
    )

    specs = {
        ReplicaType.MASTER: ReplicaSpec(
            replicas=1,
            restart_policy=RestartPolicy.ON_FAILURE,
            template=ProcessTemplate(
                module="pytorch_operator_tpu.workloads.noop"
            ),
        ),
    }
    if replicas > 1:
        specs[ReplicaType.WORKER] = ReplicaSpec(
            replicas=replicas - 1,
            restart_policy=RestartPolicy.ON_FAILURE,
            template=ProcessTemplate(
                module="pytorch_operator_tpu.workloads.noop"
            ),
        )
    return TPUJob(
        metadata=ObjectMeta(name=f"bench-{i:05d}"),
        spec=TPUJobSpec(
            replica_specs=specs, remediation=RemediationPolicy()
        ),
    )


def _io_delta(store, before: Dict[str, int]) -> Dict[str, int]:
    after = store.io.snapshot()
    return {k: after[k] - before[k] for k in after}


def _daemon_pass(sup) -> None:
    # The tpujob-supervisor loop body, minus the sleep.
    sup.store.rescan()
    sup.process_deletion_markers()
    sup.process_scale_markers()
    sup.process_suspend_markers()
    sup.process_apply_markers()
    sup.sync_once()


def _double_spawns(sups) -> int:
    """Jobs with ACTIVE replicas in more than one live supervisor's
    runner — the structural double-reconcile detector (each supervisor
    has its own FakeRunner, so a job double-reconciled across the shard
    split shows up as two worlds)."""
    owners: Dict[str, set] = {}
    for si, sup in enumerate(sups):
        for h in sup.runner.list_all():
            if h.is_active():
                owners.setdefault(h.job_key, set()).add(si)
    return sum(1 for v in owners.values() if len(v) > 1)


def bench_mode(
    n_jobs: int,
    mode: str,
    passes: int,
    state_dir: Path,
    log=print,
) -> dict:
    """One single-supervisor (N, mode) cell: build a supervisor, churn N
    jobs to RUNNING, measure idle passes, then finish everything and
    measure the drain."""
    from ..api.types import ReplicaPhase
    from ..controller.runner import FakeRunner
    from ..controller.supervisor import Supervisor

    cached = mode == "cached"
    sup = Supervisor(
        state_dir=state_dir,
        runner=FakeRunner(),
        persist=True,
        cached_store=cached,
        parallel_sync=cached,
    )

    try:
        # ---- submit + launch churn ----
        t0 = time.perf_counter()
        for i in range(n_jobs):
            sup.submit(_make_job(i))
        submit_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        _daemon_pass(sup)  # creates every world
        launch_pass_s = time.perf_counter() - t0
        for h in sup.runner.list_all():
            if h.phase == ReplicaPhase.PENDING:
                sup.runner.set_phase(h.name, ReplicaPhase.RUNNING)
        _daemon_pass(sup)  # observes RUNNING, sets conditions

        # ---- steady-state idle passes (the headline) ----
        latencies_ms: List[float] = []
        io_per_pass: List[Dict[str, int]] = []
        watch_before = sup.watch.io.snapshot()
        rem_before = sup.remediation.io.snapshot()
        pool_max_seen = sup._sync_workers
        for _ in range(passes):
            before = sup.store.io.snapshot()
            t0 = time.perf_counter()
            _daemon_pass(sup)
            latencies_ms.append(1000 * (time.perf_counter() - t0))
            io_per_pass.append(_io_delta(sup.store, before))
            pool_max_seen = max(pool_max_seen, sup._sync_workers)
        watch_after = sup.watch.io.snapshot()
        rem_after = sup.remediation.io.snapshot()

        # ---- finish churn: every master succeeds, jobs complete ----
        for h in sup.runner.list_all():
            sup.runner.set_phase(h.name, ReplicaPhase.SUCCEEDED, exit_code=0)
        t0 = time.perf_counter()
        _daemon_pass(sup)
        finish_pass_s = time.perf_counter() - t0
        unfinished = sum(1 for j in sup.list_jobs() if not j.is_finished())

        idle_reads = statistics.mean(p["reads"] for p in io_per_pass)
        idle_writes = statistics.mean(p["writes"] for p in io_per_pass)
        idle_scans = statistics.mean(p["scans"] for p in io_per_pass)
        idle_serializations = statistics.mean(
            p["serializations"] for p in io_per_pass
        )
        result = {
            "mode": mode,
            "jobs": n_jobs,
            "replicas": 1,
            "supervisors": 1,
            "passes": passes,
            "pass_ms_p50": round(_percentile(latencies_ms, 0.50), 3),
            "pass_ms_p99": round(_percentile(latencies_ms, 0.99), 3),
            "pass_ms_mean": round(statistics.mean(latencies_ms), 3),
            "idle_reads_per_pass": round(idle_reads, 2),
            "idle_writes_per_pass": round(idle_writes, 2),
            "idle_scans_per_pass": round(idle_scans, 2),
            "idle_serializations_per_pass": round(idle_serializations, 2),
            # Live health engine (obs/watch.py): idle jobs never report,
            # so the watch must neither append alert-log lines nor even
            # evaluate rules across the idle passes — both pinned at
            # zero by the bench_smoke lane.
            "idle_watch_log_appends": (
                watch_after["log_appends"] - watch_before["log_appends"]
            ),
            "idle_watch_evaluations": (
                watch_after["evaluations"] - watch_before["evaluations"]
            ),
            # Remediation engine (controller/remediation.py): every
            # bench job is ARMED, nothing fires — so across the idle
            # passes the engine must append no audit records and take
            # no actions (zero extra I/O; only the in-memory candidate
            # walk, counted as evaluations).
            "idle_remediation_log_appends": (
                rem_after["log_appends"] - rem_before["log_appends"]
            ),
            "idle_remediation_actions": (
                rem_after["actions"] - rem_before["actions"]
            ),
            # One runner → structurally impossible; recorded so EVERY
            # cell in the artifact carries the pin.
            "double_reconciles": 0,
            # Autoscaler bounds (controller/autoscale.py): the pool may
            # never exceed its ceiling and must sit at the floor after
            # an idle streak.
            "sync_pool_floor": sup._pool_scaler.floor,
            "sync_pool_ceiling": sup._pool_scaler.ceiling,
            "sync_pool_max_seen": pool_max_seen,
            "sync_pool_final": sup._sync_workers,
            "submit_s": round(submit_s, 3),
            "launch_pass_s": round(launch_pass_s, 3),
            "finish_pass_s": round(finish_pass_s, 3),
            "unfinished_after_drain": unfinished,
        }
        log(
            f"[ctrlplane] N={n_jobs:5d} {mode:7s} "
            f"pass p50={result['pass_ms_p50']:9.3f}ms "
            f"p99={result['pass_ms_p99']:9.3f}ms "
            f"idle reads/pass={idle_reads:8.1f} "
            f"writes/pass={idle_writes:8.1f}"
        )
        return result
    finally:
        sup.shutdown()


def bench_sharded(
    n_jobs: int,
    supervisors: int,
    passes: int,
    state_dir: Path,
    replicas: int = 1,
    churn_markers: int = 0,
    shards: Optional[int] = None,
    lease_ttl: float = 5.0,
    sync_workers_max: int = 16,
    log=print,
) -> dict:
    """One sharded cell: S supervisors (each with its own FakeRunner —
    its own 'host') over ONE state dir, job space split by shard
    leases. Measures per-supervisor pass latency (what each daemon
    pays for its share), per-supervisor idle store I/O, the structural
    ``double_reconciles`` count, and optionally marker-heavy churn."""
    from ..api.types import ReplicaPhase
    from ..controller.runner import FakeRunner
    from ..controller.store import JobStore
    from ..controller.supervisor import Supervisor

    shards = shards or max(4 * supervisors, 4)
    sups = [
        Supervisor(
            state_dir=state_dir,
            runner=FakeRunner(),
            persist=True,
            cached_store=True,
            parallel_sync=True,
            shards=shards,
            supervisor_id=f"bench-sup-{i}",
            lease_ttl=lease_ttl,
            sync_workers_max=sync_workers_max,
        )
        for i in range(supervisors)
    ]
    try:
        # ---- settle: tick until the fair-share split is stable ----
        t_settle0 = time.perf_counter()
        deadline = time.monotonic() + max(10 * lease_ttl, 20.0)
        while time.monotonic() < deadline:
            for sup in sups:
                _daemon_pass(sup)
            owned = [len(sup.shards.owned) for sup in sups]
            if sum(owned) == shards and all(n > 0 for n in owned):
                break
            time.sleep(min(0.05, lease_ttl / 20))
        settle_s = time.perf_counter() - t_settle0
        shard_split = {
            sup.identity: sorted(sup.shards.owned) for sup in sups
        }

        # ---- submit via one supervisor; the rest discover by rescan ----
        t0 = time.perf_counter()
        for i in range(n_jobs):
            sups[0].submit(_make_job(i, replicas))
        submit_s = time.perf_counter() - t0

        t0 = time.perf_counter()
        for sup in sups:
            _daemon_pass(sup)  # each creates the worlds of ITS shards
        launch_pass_s = time.perf_counter() - t0
        for sup in sups:
            for h in sup.runner.list_all():
                if h.phase == ReplicaPhase.PENDING:
                    sup.runner.set_phase(h.name, ReplicaPhase.RUNNING)
        for sup in sups:
            _daemon_pass(sup)  # observes RUNNING, sets conditions
        for sup in sups:
            # One settling pass: the steady fast-path caches converge a
            # round after the RUNNING transition; "idle" measurement
            # means steady state, not the transition into it.
            _daemon_pass(sup)
        double_after_launch = _double_spawns(sups)
        jobs_per_sup = [
            len({h.job_key for h in sup.runner.list_all()}) for sup in sups
        ]

        # ---- steady-state idle passes, per supervisor ----
        # All S supervisors share THIS process; in production each is
        # its own process on its own host. Freeze the launch-time heap
        # (jobs × S stores) so one supervisor's pass latency is not
        # billed for gen-2 GC walks over the other's objects — the
        # Instagram gc.freeze pattern, unfrozen after the measurement.
        import gc

        gc.collect()
        gc.freeze()
        lat_ms: List[List[float]] = [[] for _ in sups]
        io_pp: List[List[Dict[str, int]]] = [[] for _ in sups]
        pool_max_seen = [sup._sync_workers for sup in sups]
        try:
            for _ in range(passes):
                for si, sup in enumerate(sups):
                    before = sup.store.io.snapshot()
                    t0 = time.perf_counter()
                    _daemon_pass(sup)
                    lat_ms[si].append(1000 * (time.perf_counter() - t0))
                    io_pp[si].append(_io_delta(sup.store, before))
                    pool_max_seen[si] = max(
                        pool_max_seen[si], sup._sync_workers
                    )
        finally:
            gc.unfreeze()

        # ---- optional marker-heavy churn passes ----
        churn_lat_ms: List[float] = []
        churn_passes = 0
        if churn_markers > 0:
            rng = random.Random(1234)
            writer = JobStore(persist_dir=state_dir / "jobs")
            churn_passes = max(5, passes // 3)
            for _ in range(churn_passes):
                # A marker storm every pass: no-op resumes and in-place
                # applies (claim-by-rename exactly-once across S
                # supervisors; worlds keep running).
                for _ in range(churn_markers):
                    i = rng.randrange(n_jobs)
                    key = f"default/bench-{i:05d}"
                    if rng.random() < 0.5:
                        writer.mark_suspend(key, False)
                    else:
                        writer.mark_apply(
                            key, _make_job(i, replicas).to_dict()
                        )
                for si, sup in enumerate(sups):
                    t0 = time.perf_counter()
                    _daemon_pass(sup)
                    churn_lat_ms.append(
                        1000 * (time.perf_counter() - t0)
                    )
                    pool_max_seen[si] = max(
                        pool_max_seen[si], sup._sync_workers
                    )
        double_after_churn = _double_spawns(sups)

        # ---- drain ----
        for sup in sups:
            for h in sup.runner.list_all():
                sup.runner.set_phase(
                    h.name, ReplicaPhase.SUCCEEDED, exit_code=0
                )
        t0 = time.perf_counter()
        for sup in sups:
            _daemon_pass(sup)
        finish_pass_s = time.perf_counter() - t0
        # Fresh observer store: each supervisor's in-memory view covers
        # only its shards; the disk is the fleet truth.
        observer = JobStore(persist_dir=state_dir / "jobs")
        unfinished = sum(
            1 for j in observer.list() if not j.is_finished()
        )

        all_lat = [x for xs in lat_ms for x in xs]
        idle_reads = [
            statistics.mean(p["reads"] for p in xs) for xs in io_pp
        ]
        idle_writes = [
            statistics.mean(p["writes"] for p in xs) for xs in io_pp
        ]
        guard_skips = sum(sup.shards.io.guard_skips for sup in sups)
        result = {
            "mode": "sharded",
            "jobs": n_jobs,
            "replicas": replicas,
            "supervisors": supervisors,
            "shards": shards,
            "lease_ttl_s": lease_ttl,
            "passes": passes,
            "settle_s": round(settle_s, 3),
            "shard_split": shard_split,
            "jobs_per_supervisor": jobs_per_sup,
            # Pooled over every supervisor's passes: each daemon runs
            # concurrently on its own host in production, so the
            # per-pass latency IS the per-supervisor cost of its share.
            "pass_ms_p50": round(_percentile(all_lat, 0.50), 3),
            "pass_ms_p99": round(_percentile(all_lat, 0.99), 3),
            "pass_ms_p50_per_supervisor": [
                round(_percentile(xs, 0.50), 3) for xs in lat_ms
            ],
            "idle_reads_per_pass_per_supervisor": [
                round(x, 2) for x in idle_reads
            ],
            "idle_writes_per_pass_per_supervisor": [
                round(x, 2) for x in idle_writes
            ],
            # THE exactly-once pin: jobs with live worlds in >1
            # supervisor (structural), plus the in-flight guard count
            # for visibility (guard skips PREVENT double reconciles).
            "double_reconciles": max(double_after_launch, double_after_churn),
            "shard_guard_skips": guard_skips,
            "churn_markers_per_pass": churn_markers,
            "churn_passes": churn_passes,
            "churn_pass_ms_p50": round(_percentile(churn_lat_ms, 0.50), 3),
            "churn_pass_ms_p99": round(_percentile(churn_lat_ms, 0.99), 3),
            "sync_pool_floor": sups[0]._pool_scaler.floor,
            "sync_pool_ceiling": sups[0]._pool_scaler.ceiling,
            "sync_pool_max_seen": max(pool_max_seen),
            "sync_pool_final": max(sup._sync_workers for sup in sups),
            "submit_s": round(submit_s, 3),
            "launch_pass_s": round(launch_pass_s, 3),
            "finish_pass_s": round(finish_pass_s, 3),
            "unfinished_after_drain": unfinished,
        }
        log(
            f"[ctrlplane] N={n_jobs:5d} sharded×{supervisors} "
            f"(M={replicas}) pass p50={result['pass_ms_p50']:9.3f}ms "
            f"p99={result['pass_ms_p99']:9.3f}ms "
            f"double_reconciles={result['double_reconciles']} "
            f"idle reads/pass={max(idle_reads):6.1f}"
        )
        return result
    finally:
        for sup in sups:
            sup.shutdown()


def _parse_cells(spec: Optional[str]) -> List[dict]:
    """``'10000:2,500x16:4'`` → [{jobs, replicas, supervisors}, ...]."""
    out: List[dict] = []
    if not spec:
        return out
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        nm, _, sups = part.partition(":")
        n, _, m = nm.partition("x")
        out.append(
            {
                "jobs": int(n),
                "replicas": int(m) if m else 1,
                "supervisors": int(sups) if sups else 1,
            }
        )
    return out


# The pinned single-supervisor baseline this artifact's flatness
# acceptance is judged against: idle pass p50 at N=1000 on the CPU box
# of PR 2, when the 10k target was set (a host-plane figure, no chip's).
BASELINE_N1000_P50_MS = 63.0
ACCEPTANCE_RATIO = 1.5


def run(
    jobs: Optional[List[int]] = None,
    passes: int = 30,
    out: Optional[str] = None,
    work_dir: Optional[str] = None,
    sharded_cells: Optional[List[dict]] = None,
    gang_cells: Optional[List[dict]] = None,
    churn_cells: Optional[List[dict]] = None,
    churn_markers: int = 200,
    lease_ttl: float = 5.0,
    log=print,
) -> dict:
    jobs = jobs or [10, 100, 1000]
    cells: List[dict] = []
    for n in jobs:
        # Fewer legacy passes at large N: each one rewrites every job
        # file; the distribution is tight, no need to burn minutes.
        legacy_passes = min(passes, 10) if n >= 1000 else passes
        for mode, n_passes in (("legacy", legacy_passes), ("cached", passes)):
            with tempfile.TemporaryDirectory(
                prefix=f"ctrlplane-{mode}-{n}-", dir=work_dir
            ) as td:
                cells.append(
                    bench_mode(n, mode, n_passes, Path(td), log=log)
                )

    for group, extra in (
        (sharded_cells or [], {}),
        (gang_cells or [], {}),
        (churn_cells or [], {"churn_markers": churn_markers}),
    ):
        for cell in group:
            with tempfile.TemporaryDirectory(
                prefix=(
                    f"ctrlplane-sharded-{cell['jobs']}x"
                    f"{cell.get('replicas', 1)}-{cell['supervisors']}-"
                ),
                dir=work_dir,
            ) as td:
                cells.append(
                    bench_sharded(
                        cell["jobs"],
                        cell["supervisors"],
                        passes,
                        Path(td),
                        replicas=cell.get("replicas", 1),
                        lease_ttl=lease_ttl,
                        log=log,
                        **extra,
                    )
                )

    by = {(c["jobs"], c["mode"]): c for c in cells}
    comparisons = []
    for n in jobs:
        legacy, cached = by.get((n, "legacy")), by.get((n, "cached"))
        if not legacy or not cached:
            continue
        comparisons.append(
            {
                "jobs": n,
                "pass_p50_speedup": round(
                    legacy["pass_ms_p50"] / max(cached["pass_ms_p50"], 1e-9), 2
                ),
                "pass_p99_speedup": round(
                    legacy["pass_ms_p99"] / max(cached["pass_ms_p99"], 1e-9), 2
                ),
                "idle_read_reduction": round(
                    legacy["idle_reads_per_pass"]
                    / max(cached["idle_reads_per_pass"], 1.0),
                    2,
                ),
                "idle_write_reduction": round(
                    legacy["idle_writes_per_pass"]
                    / max(cached["idle_writes_per_pass"], 1.0),
                    2,
                ),
            }
        )

    # Flatness acceptance: the biggest 2-supervisor sharded cell's idle
    # p50 vs the pinned N=1000 single-supervisor baseline.
    acceptance = None
    two_sup = [
        c
        for c in cells
        if c["mode"] == "sharded"
        and c["supervisors"] == 2
        and c.get("replicas", 1) == 1
        and not c.get("churn_markers_per_pass")
    ]
    if two_sup:
        headline = max(two_sup, key=lambda c: c["jobs"])
        ratio = headline["pass_ms_p50"] / BASELINE_N1000_P50_MS
        acceptance = {
            "baseline_n1000_1sup_p50_ms": BASELINE_N1000_P50_MS,
            "jobs": headline["jobs"],
            "supervisors": 2,
            "pass_ms_p50": headline["pass_ms_p50"],
            "ratio_vs_baseline": round(ratio, 3),
            "target_ratio": ACCEPTANCE_RATIO,
            "pass": ratio <= ACCEPTANCE_RATIO,
            "double_reconciles_all_cells": max(
                c["double_reconciles"] for c in cells
            ),
        }

    result = {
        "bench": "control_plane",
        "metric": "supervisor_pass_latency_ms",
        "protocol": (
            "N synthetic jobs (Master + M-1 Workers) on FakeRunner; full "
            "daemon loop body per pass (rescan + 4 marker scans + "
            "sync_once); idle = all jobs Running, no transitions. legacy "
            "= JobStore(cache=False) + serial pass (pre-cache behavior); "
            "cached = dirty-tracking store + scandir snapshot + steady "
            "fast path + autoscaled pool; sharded = S supervisors, one "
            "state dir, per-shard store leases (each supervisor has its "
            "own runner — per-supervisor pass latency is the cost of its "
            "share; the launch-time heap is gc.freeze'd across the idle "
            "measurement since production runs one PROCESS per "
            "supervisor, not S heaps in one). churn cells add a "
            "per-pass marker storm "
            "(suspend/apply no-ops, rename-claimed exactly-once). "
            "double_reconciles = jobs with live worlds in >1 supervisor."
        ),
        "cells": cells,
        "comparisons": comparisons,
        "acceptance": acceptance,
    }
    if out:
        Path(out).write_text(json.dumps(result, indent=2) + "\n")
        log(f"[ctrlplane] wrote {out}")
    return result


DEFAULT_SHARDED = "10000:1,10000:2,10000:4"
DEFAULT_GANGS = "500x16:2"
DEFAULT_CHURN = "2000:2"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--jobs",
        default="10,100,1000",
        help="comma-separated fleet sizes for the single-supervisor "
        "legacy-vs-cached cells",
    )
    p.add_argument(
        "--passes", type=int, default=30, help="idle passes per cell"
    )
    p.add_argument(
        "--sharded-cells",
        default=DEFAULT_SHARDED,
        help="multi-supervisor cells as N:S (jobs:supervisors), e.g. "
        "'10000:2,10000:4'; empty string disables",
    )
    p.add_argument(
        "--gang-cells",
        default=DEFAULT_GANGS,
        help="wide-gang cells as NxM:S (jobs x replicas : supervisors), "
        "e.g. '500x16:2'; empty string disables",
    )
    p.add_argument(
        "--churn-cells",
        default=DEFAULT_CHURN,
        help="marker-heavy churn cells as N:S; empty string disables",
    )
    p.add_argument(
        "--churn-markers",
        type=int,
        default=200,
        help="markers written per churn pass",
    )
    p.add_argument(
        "--lease-ttl",
        type=float,
        default=5.0,
        help="shard-lease TTL for the sharded cells",
    )
    p.add_argument("--out", default=None, help="artifact path (JSON)")
    p.add_argument(
        "--work-dir",
        default=None,
        help="where the throwaway state dirs live (default: system tmp)",
    )
    args = p.parse_args(argv)
    try:
        jobs = [int(x) for x in args.jobs.split(",") if x.strip()]
    except ValueError:
        print(f"--jobs must be comma-separated ints: {args.jobs!r}",
              file=sys.stderr)
        return 2
    try:
        sharded = _parse_cells(args.sharded_cells)
        gangs = _parse_cells(args.gang_cells)
        churn = _parse_cells(args.churn_cells)
    except ValueError:
        print("--sharded-cells/--gang-cells/--churn-cells must be "
              "N[xM][:S] lists", file=sys.stderr)
        return 2
    result = run(
        jobs=jobs,
        passes=args.passes,
        out=args.out,
        work_dir=args.work_dir,
        sharded_cells=sharded,
        gang_cells=gangs,
        churn_cells=churn,
        churn_markers=args.churn_markers,
        lease_ttl=args.lease_ttl,
    )
    print(
        json.dumps(
            {
                "comparisons": result["comparisons"],
                "acceptance": result["acceptance"],
            }
        )
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
