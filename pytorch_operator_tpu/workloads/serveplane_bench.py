"""Serve-plane benchmark: routed goodput, shed behavior, latency tails.

The serve plane's claim is that ONE front spool fans out across N
engine replicas with admission control and retry-on-death, and that
the router adds nothing when no serving job exists. This bench proves
both with numbers, end to end through the REAL stack: a Supervisor
with its SubprocessRunner spawns ``workloads/serve_stub`` replicas
(the jax-free engine stand-in with serve.py's exact service contract),
the supervisor-hosted router (serving/router.py) does discovery /
admission / least-loaded dispatch / exactly-once publication, and an
open-loop Poisson client drives the front spool at a FIXED offered
load while replicas die underneath it.

Cells: replicas {1, 2, 4} x scenario {healthy, kill_replica,
fail_engine_step}. The stub's capacity model is exact — ``slots``
concurrent requests, one token per slot per ``tpot_ms`` block — so a
replica saturates at ``slots / (max_new_tokens * tpot_ms)`` requests
per second and the offered rate can be placed deliberately ABOVE the
small cells' capacity: the 1-replica cell sheds (that is the admission
control working), the 4-replica cell absorbs the same offered load,
and the goodput ratio between them is the scaling acceptance.

Per cell the artifact (``--out``) reports goodput,
shed rate (split by depth/deadline), TTFT / per-token / queue-wait
p50/p99, re-routes, duplicates (pinned 0 — ``respond_once``), and lost
requests (pinned 0 — every submit gets exactly one response, overload
and chaos included). An idle-overhead cell runs a non-serving fleet
and pins the router to ZERO work: no ticks, no ``<state>/serve`` dir.

Accounting closure is the same code the router enforces
(serving/slo.py ``SLOStats``): every response lands in exactly one
bucket and ``accounted == offered`` in every cell.

Usage:
    python -m pytorch_operator_tpu.workloads.serveplane_bench \
        [--replicas 1,2,4] [--scenarios healthy,kill_replica,fail_engine_step] \
        [--rate 85] [--duration 6] [--out serveplane.json]
    tpujob bench-serve-plane ...
"""

from __future__ import annotations

import argparse
import json
import random
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import List, Optional

SCENARIOS = ("healthy", "kill_replica", "fail_engine_step")


def _percentile(values: List[float], q: float) -> float:
    if not values:
        return 0.0
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, round(q * (len(xs) - 1))))
    return xs[idx]


def _make_serve_job(
    name: str,
    replicas: int,
    *,
    slots: int,
    tpot_ms: float,
    idle_timeout: float,
    max_queue_depth: int,
    deadline_s: float,
    retry_limit: int,
    transport: str = "spool",
    router_shards: int = 0,
    slo_target: float = 0.0,
    burn_window_s: float = 0.0,
    alerts: Optional[dict] = None,
    remediation=None,
):
    """A serving job of ``replicas`` engine replicas: Master(1) +
    Worker(replicas-1) — validation pins Master at exactly one, and the
    router treats every active handle as an engine regardless of type."""
    from ..api.types import (
        AlertPolicy,
        ObjectMeta,
        ObservabilityPolicy,
        ProcessTemplate,
        ReplicaSpec,
        ReplicaType,
        RestartPolicy,
        ServingPolicy,
        ServingSLOPolicy,
        TPUJob,
        TPUJobSpec,
    )

    template = ProcessTemplate(
        module="pytorch_operator_tpu.workloads.serve_stub",
        args=[
            "--slots", str(slots),
            "--tpot-ms", str(tpot_ms),
            "--idle-timeout", str(idle_timeout),
            "--report-every", "0.2",
        ],
    )
    specs = {
        ReplicaType.MASTER: ReplicaSpec(
            replicas=1,
            restart_policy=RestartPolicy.ON_FAILURE,
            template=template,
        ),
    }
    if replicas > 1:
        specs[ReplicaType.WORKER] = ReplicaSpec(
            replicas=replicas - 1,
            restart_policy=RestartPolicy.ON_FAILURE,
            template=template,
        )
    return TPUJob(
        metadata=ObjectMeta(name=name),
        spec=TPUJobSpec(
            replica_specs=specs,
            serving=ServingPolicy(
                slo=ServingSLOPolicy(
                    max_queue_depth=max_queue_depth,
                    deadline_s=deadline_s,
                    retry_limit=retry_limit,
                    target=slo_target,
                    burn_window_s=burn_window_s,
                ),
                transport=transport,
                router_shards=router_shards,
            ),
            observability=(
                ObservabilityPolicy(alerts=AlertPolicy(**alerts))
                if alerts
                else None
            ),
            remediation=remediation,
        ),
    )


def bench_cell(
    replicas: int,
    scenario: str,
    *,
    rate: float,
    duration: float,
    slots: int,
    tpot_ms: float,
    max_new_tokens: int,
    max_queue_depth: int,
    deadline_s: float,
    retry_limit: int,
    idle_timeout: float,
    state_dir: Path,
    transport: str = "spool",
    router_shards: int = 0,
    label: Optional[str] = None,
    seed: int = 7,
    slo_target: float = 0.0,
    burn_window_s: float = 0.0,
    alerts: Optional[dict] = None,
    remediation=None,
    log=print,
) -> dict:
    """One (replicas, scenario) cell through the full serve plane."""
    from .. import faults
    from ..controller.store import key_to_fs
    from ..controller.supervisor import Supervisor
    from ..obs.trace import records_emitted
    from ..serving import Spool, make_request
    from ..serving.router import front_spool_dir, serve_root_dir
    from ..serving.slo import SLOStats

    # The serve-path zero-overhead pin: tracing is off in the bench
    # (no TPUJOB_TRACE_DIR), so this process — client enqueues plus the
    # supervisor-hosted router — must emit exactly zero span records.
    span_records0 = records_emitted()
    sup = Supervisor(state_dir=state_dir, poll_interval=0.02)
    stop = threading.Event()
    pump_errors: List[str] = []

    def pump() -> None:
        while not stop.is_set():
            try:
                sup.sync_once()
            except Exception as e:  # surfaced in the cell record
                pump_errors.append(repr(e))
            stop.wait(sup.poll_interval)

    # Worker-side faults ride into replicas via TPUJOB_FAULT_PLAN at
    # SPAWN time, so the engine-step plan must be armed before submit.
    # One fault per replica injector: each replica aborts exactly one
    # decode block mid-window, answering its whole in-flight batch with
    # error responses (the exactly-once contract under engine failure).
    engine_fault_nth = max(5, int(0.15 * duration * 1000.0 / tpot_ms))
    if scenario == "fail_engine_step":
        faults.arm(
            faults.FaultPlan(
                seed=seed,
                faults=[
                    faults.Fault(kind="fail_engine_step", nth=engine_fault_nth)
                ],
            )
        )

    pump_thread = threading.Thread(target=pump, daemon=True)
    try:
        cell_name = label or f"{scenario}x{replicas}"
        job = _make_serve_job(
            f"serve-bench-{cell_name.replace('_', '-')}",
            replicas,
            slots=slots,
            tpot_ms=tpot_ms,
            idle_timeout=idle_timeout,
            max_queue_depth=max_queue_depth,
            deadline_s=deadline_s,
            retry_limit=retry_limit,
            transport=transport,
            router_shards=router_shards,
            slo_target=slo_target,
            burn_window_s=burn_window_s,
            alerts=alerts,
            remediation=remediation,
        )
        key = sup.submit(job)
        pump_thread.start()

        # Readiness: every replica spawned AND reporting (first_step /
        # serve beats land in the status dir) — the idle_timeout clock
        # starts inside the replica loop, so arrivals must not lag it.
        status_dir = Path(state_dir) / "status" / key_to_fs(key)
        launch_deadline = time.monotonic() + 90.0
        ready = False
        while time.monotonic() < launch_deadline:
            active = [h for h in sup.runner.list_for_job(key) if h.is_active()]
            reported = (
                len(list(status_dir.glob("*.jsonl")))
                if status_dir.is_dir()
                else 0
            )
            if len(active) >= replicas and reported >= replicas:
                ready = True
                break
            time.sleep(0.02)
        if not ready:
            raise RuntimeError(
                f"cell {scenario}x{replicas}: replicas not ready "
                f"(pump errors: {pump_errors[:3]})"
            )

        # Controller-side kill: armed at window start so the pass count
        # ``at`` schedules against begins NOW (the supervisor's fault
        # pass counter only ticks while a plan is armed). Kill a worker
        # when the job has one (master survives; the job still ends
        # Succeeded), the lone master otherwise.
        if scenario == "kill_replica":
            kill_at = max(3, int(0.25 * duration / sup.poll_interval))
            target = "worker-0" if replicas > 1 else "master-0"
            faults.arm(
                faults.FaultPlan(
                    seed=seed,
                    faults=[
                        faults.Fault(
                            kind="kill_replica", target=target, at=kill_at
                        )
                    ],
                )
            )

        front = Spool(
            front_spool_dir(serve_root_dir(state_dir), key, job.spec.serving)
        )

        # ---- open-loop Poisson arrivals at the FIXED offered rate ----
        # Arrivals due at a wake ride ONE batch frame (enqueue_batch:
        # one tmp write + fsync + rename for the whole burst) — the
        # client-side half of the batched-framing syscall collapse; a
        # lone arrival still goes through the classic single-file
        # submit path so both framings stay exercised.
        rng = random.Random(seed * 7919 + replicas)
        stats = SLOStats()
        start = time.time()
        end = start + duration
        t_next = start
        rids: List[str] = []
        # Warm-up tracking: the rids submitted inside the FIRST second
        # of the window — their TTFT tail is where a cold transport
        # (ring files created at first dispatch) used to spike.
        early_rids: set = set()
        # Recovery tracking: the rids submitted in the LAST quarter of
        # the window — where an armed remediation policy has already
        # grown the fleet, so their ok-rate is the recovered goodput.
        late_rids: set = set()
        late_start = start + 0.75 * duration
        while True:
            now = time.time()
            if now >= end:
                break
            if now < t_next:
                time.sleep(min(0.002, t_next - now))
                continue
            due: List[dict] = []
            while t_next <= now:
                due.append(
                    make_request(prompt_len=4,
                                 max_new_tokens=max_new_tokens)
                )
                t_next += rng.expovariate(rate)
            if now - start <= 1.0:
                early_rids.update(r["id"] for r in due)
            if now >= late_start:
                late_rids.update(r["id"] for r in due)
            if len(due) == 1:
                front.enqueue(due[0])
                rids.append(due[0]["id"])
            elif due:
                rids.extend(front.enqueue_batch(due))
        stats.offered = len(rids)

        # ---- collect: EVERY submit gets exactly one response ----
        # ONE responses/ scan per poll (not one stat per pending id):
        # the collection loop stays O(responses) however large the
        # saturation cell's in-flight population gets.
        pending = set(rids)
        early_ttfts: List[float] = []
        late_ok = 0
        collect_deadline = time.monotonic() + deadline_s + max(30.0, 4 * duration)
        while pending and time.monotonic() < collect_deadline:
            done = []
            try:
                arrived = [
                    p.stem for p in front.responses.iterdir()
                    if p.suffix == ".json"
                ]
            except FileNotFoundError:
                arrived = []
            for rid in arrived:
                if rid not in pending:
                    continue
                resp = front.read_response(rid)
                if resp is not None:
                    bucket = stats.account(resp)
                    done.append(rid)
                    if rid in early_rids and resp.get("ttft_ms") is not None:
                        early_ttfts.append(float(resp["ttft_ms"]))
                    if rid in late_rids and bucket == "ok":
                        late_ok += 1
            pending.difference_update(done)
            if pending:
                time.sleep(0.02)
        stats.finish()
        lost = len(pending)

        # Duplicates: respond_once makes a second response for a known
        # id structurally impossible; a response for an id nobody
        # submitted would be the other way to violate exactly-once.
        files = {p.stem for p in front.responses.glob("*.json")}
        stats.duplicates = len(files - set(rids))

        # ---- teardown: replicas idle out, master succeeds ----
        finish_deadline = time.monotonic() + idle_timeout + 60.0
        finished = False
        while time.monotonic() < finish_deadline:
            j = sup.store.get(key)
            if j is not None and j.is_finished():
                finished = True
                break
            time.sleep(0.05)
        stop.set()
        pump_thread.join(timeout=10.0)

        # TTFT tail bound: an OK response's LAST dispatch passed the
        # deadline check, and after dispatch it waits out at most the
        # admitted backlog on the surviving replicas plus its own
        # decode — deadline-shed is what keeps the tail finite.
        surviving = max(
            1, replicas - (1 if scenario == "kill_replica" else 0)
        )
        bound_ms = (
            1000.0 * deadline_s
            + (max_queue_depth / max(1, slots * surviving) + 1)
            * max_new_tokens
            * tpot_ms
            + 500.0
        )
        summary = stats.summary()
        cell = {
            "cell": cell_name,
            "scenario": scenario,
            "replicas": replicas,
            "transport": transport,
            "router_shards": router_shards,
            "offered_rate_rps": rate,
            "duration_s": duration,
            "slots": slots,
            "tpot_ms": tpot_ms,
            "max_new_tokens": max_new_tokens,
            "replica_capacity_rps": round(
                slots / (max_new_tokens * tpot_ms / 1000.0), 2
            ),
            "slo": {
                "max_queue_depth": max_queue_depth,
                "deadline_s": deadline_s,
                "retry_limit": retry_limit,
            },
            **summary,
            "lost": lost,
            "job_finished": finished,
            "router_io": sup.router.io_snapshot(),
            "span_records": records_emitted() - span_records0,
            "first_second_ttft_p99_ms": (
                round(_percentile(early_ttfts, 0.99), 1)
                if early_ttfts
                else None
            ),
            "first_second_n": len(early_ttfts),
            "job_key": key,
            "pump_errors": len(pump_errors),
            "ttft_p99_bound_ms": round(bound_ms, 1),
            "ttft_p99_bounded": (
                summary["ttft_ms_p99"] is None
                or summary["ttft_ms_p99"] <= bound_ms
            ),
        }
        # Recovered goodput: ok-rate over the last quarter's arrivals
        # (where a remediation grow, if armed, has already landed).
        cell["late_window_offered"] = len(late_rids)
        cell["late_window_ok"] = late_ok
        cell["late_window_ok_rate"] = round(
            late_ok / max(1, len(late_rids)), 4
        )
        cell["late_window_goodput_rps"] = round(
            late_ok / max(1e-9, 0.25 * duration), 3
        )
        if alerts:
            # The live watch's verdicts for this cell, straight from
            # the on-disk transition log — the burn-smoke lifecycle
            # (pending -> firing -> resolved) reads off this list.
            from ..obs.watch import load_alert_log

            cell["slo_burn_transitions"] = [
                r.get("state")
                for r in load_alert_log(state_dir, key)
                if r.get("rule") == "slo_burn"
            ]
        if remediation is not None:
            # The closed loop's audit trail for this cell: every
            # alert→decision→action→outcome the engine committed, read
            # back from the same on-disk log `tpujob remediations`
            # shows (condensed — the full records stay in the log).
            from ..controller.remediation import load_remediation_log

            cell["remediations"] = [
                {
                    "rule": r.get("rule"),
                    "action": r.get("action"),
                    "outcome": r.get("outcome"),
                    "generation": r.get("generation"),
                    "detail": r.get("detail"),
                }
                for r in load_remediation_log(state_dir, key)
            ]
        log(
            f"[serveplane] {cell_name:>20s} "
            f"offered={cell['offered']:4d} ok={cell['ok']:4d} "
            f"shed={cell['shed']:4d} errors={cell['errors']:3d} "
            f"rerouted={cell['rerouted']:2d} lost={lost} "
            f"goodput={cell['goodput_rps']:6.1f}rps "
            f"ttft p99={cell['ttft_ms_p99'] or 0:7.1f}ms"
        )
        return cell
    finally:
        faults.disarm()
        stop.set()
        if pump_thread.is_alive():
            pump_thread.join(timeout=10.0)
        sup.shutdown()


def _make_noop_job(i: int):
    from ..api.types import (
        ObjectMeta,
        ProcessTemplate,
        ReplicaSpec,
        ReplicaType,
        RestartPolicy,
        TPUJob,
        TPUJobSpec,
    )

    return TPUJob(
        metadata=ObjectMeta(name=f"idle-{i:04d}"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.MASTER: ReplicaSpec(
                    replicas=1,
                    restart_policy=RestartPolicy.ON_FAILURE,
                    template=ProcessTemplate(
                        module="pytorch_operator_tpu.workloads.noop"
                    ),
                )
            }
        ),
    )


def bench_idle_overhead(
    n_jobs: int, passes: int, state_dir: Path, log=print
) -> dict:
    """The zero-overhead pin: a fleet with NO serving jobs must cost
    the router nothing — zero ticks, zero scans, and ``<state>/serve``
    never materializes on disk."""
    from ..api.types import ReplicaPhase
    from ..controller.runner import FakeRunner
    from ..controller.supervisor import Supervisor

    sup = Supervisor(state_dir=state_dir, runner=FakeRunner())
    try:
        for i in range(n_jobs):
            sup.submit(_make_noop_job(i))
        sup.sync_once()
        for h in sup.runner.list_all():
            if h.phase == ReplicaPhase.PENDING:
                sup.runner.set_phase(h.name, ReplicaPhase.RUNNING)
        sup.sync_once()
        lat_ms: List[float] = []
        for _ in range(passes):
            t0 = time.perf_counter()
            sup.sync_once()
            lat_ms.append(1000 * (time.perf_counter() - t0))
        io = sup.router.io_snapshot()
        cell = {
            "cell": "idle_overhead",
            "jobs": n_jobs,
            "passes": passes,
            "pass_ms_p50": round(_percentile(lat_ms, 0.50), 3),
            "pass_ms_p99": round(_percentile(lat_ms, 0.99), 3),
            "router_io": io,
            "router_io_total": sum(io.values()),
            "serve_dir_exists": (Path(state_dir) / "serve").exists(),
        }
        log(
            f"[serveplane] idle overhead: {n_jobs} non-serving jobs, "
            f"{passes} passes — router_io={cell['router_io_total']} "
            f"serve_dir={cell['serve_dir_exists']} "
            f"pass p50={cell['pass_ms_p50']}ms"
        )
        return cell
    finally:
        sup.shutdown()


def bench_burn_smoke(state_dir: Path, log=print) -> dict:
    """Sustained overload against a tight SLO: offered rate ~2.6x one
    replica's capacity with a 150 ms deadline, so deadline/depth sheds
    burn the error budget hard. Pins the burn-rate alert lifecycle:
    ``slo_burn`` FIRES while the budget drains (for_s hysteresis), then
    RESOLVES once the load stops and the 1 s fast window decays — both
    transitions land in the on-disk alert log that ``tpujob alerts``
    and ``tpujob why`` read."""
    cell = bench_cell(
        1,
        "healthy",
        rate=260.0,
        duration=1.5,
        slots=4,
        tpot_ms=10.0,
        max_new_tokens=4,
        max_queue_depth=32,
        deadline_s=0.15,
        retry_limit=1,
        idle_timeout=4.0,
        state_dir=state_dir,
        label="burn_smoke",
        slo_target=0.99,
        # A 1 s fast window (vs the 30 s default) so the burn decays —
        # and the alert resolves — inside the cell's own teardown.
        burn_window_s=1.0,
        alerts={
            "for_s": 0.5,
            "clear_s": 0.6,
            "thresholds": {"slo_burn_samples": 2},
        },
        log=log,
    )
    states = cell.get("slo_burn_transitions", [])
    cell["burn_alert_fired"] = "firing" in states
    cell["burn_alert_resolved"] = "resolved" in states
    # Offline parity: the postmortem reads the SAME alert log, so
    # `tpujob why` tells the story after the job is gone.
    from ..obs import analyze as obs_analyze

    report = obs_analyze.analyze(state_dir, cell["job_key"])
    cell["why_cites_slo_burn"] = any(
        a.get("rule") == "slo_burn" for a in report.get("alerts", [])
    )
    log(
        f"[serveplane] burn smoke: shed={cell['shed']} "
        f"transitions={states} why_cites={cell['why_cites_slo_burn']}"
    )
    return cell


def bench_overload_remediation(state_dir: Path, log=print) -> dict:
    """Sustained overload with the loop CLOSED: the same ~2.6x
    overload as the burn smoke, but the job carries a live (dry_run
    off) remediation policy — ``slo_burn`` fires, the engine grows the
    serving fleet (1 → 2 → 4 under grow-fast doubling), the grown
    capacity (4 x 100 rps) clears the 260 rps offered rate, and the
    last quarter of the window measures RECOVERED goodput. The pins:
    at least one applied ``scale_up`` in the audit log, late-window
    ok-rate at/above the recovery bar, and the burn alert resolving
    (burn back under 1.0) once the grown fleet drains the queue."""
    from ..api.types import RemediationPolicy

    duration = 8.0
    rate = 260.0
    cell = bench_cell(
        1,
        "healthy",
        rate=rate,
        duration=duration,
        slots=4,
        tpot_ms=10.0,
        max_new_tokens=4,
        max_queue_depth=64,
        deadline_s=1.0,
        retry_limit=1,
        idle_timeout=4.0,
        state_dir=state_dir,
        label="overload_remediation",
        slo_target=0.99,
        burn_window_s=1.0,
        alerts={
            "for_s": 0.5,
            "clear_s": 0.6,
            "thresholds": {"slo_burn_samples": 2},
        },
        # The closed loop under test: grow on burn, short cooldown so
        # both doublings land inside the window, shrink never (the
        # idle watermark outlives the cell).
        remediation=RemediationPolicy(
            dry_run=False,
            cooldown_s=1.0,
            backoff=1.0,
            scale_max=4,
            idle_s=600.0,
        ),
        log=log,
    )
    states = cell.get("slo_burn_transitions", [])
    grows = [
        r
        for r in cell.get("remediations", [])
        if r["action"] == "scale_up" and r["outcome"] == "applied"
    ]
    cell["burn_alert_fired"] = "firing" in states
    cell["burn_alert_resolved"] = "resolved" in states
    cell["remediation_grows"] = len(grows)
    cell["final_replicas"] = grows[-1]["detail"]["to"] if grows else 1
    # Recovery bar: the grown fleet's capacity (scale_max x 100 rps)
    # clears the offered rate, so the last-quarter arrivals should
    # mostly succeed — vs the ungrown burn smoke, which sheds ~60%
    # all the way through.
    cell["recovery_target_ok_rate"] = 0.7
    cell["recovered"] = (
        bool(grows)
        and cell["late_window_ok_rate"] >= cell["recovery_target_ok_rate"]
    )
    log(
        f"[serveplane] overload remediation: grows={len(grows)} "
        f"-> {cell['final_replicas']} replicas, late ok-rate="
        f"{cell['late_window_ok_rate']} transitions={states}"
    )
    return cell


# Router-saturation profile defaults: per-replica capacity is cranked
# far past the offered rate (slots/(max_new_tokens*tpot_ms) = 2000
# rps/replica), so the cell measures the ROUTING path — sharded
# workers + shm rings + batched framing — not the stubs' clock. The
# kill variant runs the same profile with a mid-window replica kill:
# exactly-once under chaos on the ring path.
SATURATION = {
    "replicas": 4,
    "scenarios": ("healthy", "kill_replica"),
    "rate": 420.0,
    "slots": 16,
    "tpot_ms": 2.0,
    "max_new_tokens": 4,
    "max_queue_depth": 512,
    "deadline_s": 5.0,
    "transport": "shmring",
    "router_shards": 4,
}


def run(
    replica_cells=(1, 2, 4),
    scenarios=SCENARIOS,
    rate: float = 85.0,
    duration: float = 6.0,
    slots: int = 4,
    tpot_ms: float = 20.0,
    max_new_tokens: int = 8,
    max_queue_depth: int = 32,
    deadline_s: float = 2.0,
    retry_limit: int = 2,
    idle_timeout: float = 4.0,
    idle_jobs: int = 20,
    idle_passes: int = 30,
    saturation: Optional[dict] = None,
    burn_smoke: bool = False,
    overload_remediation: bool = False,
    out: Optional[str] = None,
    work_dir: Optional[str] = None,
    seed: int = 7,
    log=print,
) -> dict:
    from ..api.types import RemediationPolicy

    cells: List[dict] = []
    for scenario in scenarios:
        for n in replica_cells:
            with tempfile.TemporaryDirectory(
                prefix=f"serveplane-{scenario}-{n}-", dir=work_dir
            ) as td:
                cells.append(
                    bench_cell(
                        n,
                        scenario,
                        rate=rate,
                        duration=duration,
                        slots=slots,
                        tpot_ms=tpot_ms,
                        max_new_tokens=max_new_tokens,
                        max_queue_depth=max_queue_depth,
                        deadline_s=deadline_s,
                        retry_limit=retry_limit,
                        idle_timeout=idle_timeout,
                        state_dir=Path(td),
                        seed=seed,
                        # Chaos cells run with the remediation engine
                        # ARMED (live, not dry-run): the exactly-once
                        # pins (duplicates == 0, lost == 0) must hold
                        # with the closed loop riding every pass.
                        remediation=(
                            RemediationPolicy(dry_run=False)
                            if scenario == "kill_replica"
                            else None
                        ),
                        log=log,
                    )
                )
    sat_cells: List[dict] = []
    if saturation is not None:
        sat = dict(SATURATION, **saturation)
        for scenario in sat["scenarios"]:
            label = (
                f"saturationx{sat['replicas']}"
                if scenario == "healthy"
                else f"saturation_{scenario}x{sat['replicas']}"
            )
            with tempfile.TemporaryDirectory(
                prefix=f"serveplane-{label}-", dir=work_dir
            ) as td:
                cell = bench_cell(
                    sat["replicas"],
                    scenario,
                    rate=sat["rate"],
                    duration=duration,
                    slots=sat["slots"],
                    tpot_ms=sat["tpot_ms"],
                    max_new_tokens=sat["max_new_tokens"],
                    max_queue_depth=sat["max_queue_depth"],
                    deadline_s=sat["deadline_s"],
                    retry_limit=retry_limit,
                    idle_timeout=idle_timeout,
                    state_dir=Path(td),
                    transport=sat["transport"],
                    router_shards=sat["router_shards"],
                    label=label,
                    seed=seed,
                    log=log,
                )
                cell["profile"] = "saturation"
                sat_cells.append(cell)
        cells.extend(sat_cells)
    burn_cell: Optional[dict] = None
    if burn_smoke:
        with tempfile.TemporaryDirectory(
            prefix="serveplane-burn-", dir=work_dir
        ) as td:
            burn_cell = bench_burn_smoke(Path(td) / "state", log=log)
    overload_cell: Optional[dict] = None
    if overload_remediation:
        with tempfile.TemporaryDirectory(
            prefix="serveplane-remediate-", dir=work_dir
        ) as td:
            overload_cell = bench_overload_remediation(
                Path(td) / "state", log=log
            )
    with tempfile.TemporaryDirectory(
        prefix="serveplane-idle-", dir=work_dir
    ) as td:
        idle = bench_idle_overhead(idle_jobs, idle_passes, Path(td), log=log)

    healthy = {
        c["replicas"]: c for c in cells if c["scenario"] == "healthy"
    }
    duplicates_total = sum(c["duplicates"] for c in cells)
    lost_total = sum(c["lost"] for c in cells)
    comparisons: dict = {
        "duplicates_total": duplicates_total,
        "lost_total": lost_total,
        "accounting_closed": all(
            c["accounted"] == c["offered"] for c in cells
        ),
        "rerouted_total": sum(c["rerouted"] for c in cells),
        "idle_router_io_zero": (
            idle["router_io_total"] == 0 and not idle["serve_dir_exists"]
        ),
        # The serve-path extension of the zero-overhead pin: with
        # tracing disabled (the bench never sets TPUJOB_TRACE_DIR),
        # client enqueues + the router emit ZERO span records.
        "span_records_total": sum(c.get("span_records", 0) for c in cells),
        "tracing_disabled_zero_span_records": all(
            c.get("span_records", 0) == 0 for c in cells
        ),
    }
    # Warm-up: rings are pre-armed at replica SPAWN (reconciler), so
    # the first second of a shmring cell must not pay ring creation
    # in its TTFT tail.
    warm_cells = [
        c
        for c in cells
        if c["transport"] == "shmring"
        and c["scenario"] == "healthy"
        and c.get("first_second_ttft_p99_ms") is not None
    ]
    if warm_cells:
        w = warm_cells[0]
        comparisons["warmup"] = {
            "cell": w["cell"],
            "first_second_ttft_p99_ms": w["first_second_ttft_p99_ms"],
            "first_second_n": w["first_second_n"],
            "rings_prearmed_at_spawn": True,
        }
    acceptance: Optional[dict] = None
    if len(healthy) >= 2:
        lo_n, hi_n = min(healthy), max(healthy)
        lo, hi = healthy[lo_n], healthy[hi_n]
        ratio = hi["goodput_rps"] / max(lo["goodput_rps"], 1e-9)
        comparisons["goodput_scaling"] = {
            "replicas_lo": lo_n,
            "replicas_hi": hi_n,
            "goodput_lo_rps": lo["goodput_rps"],
            "goodput_hi_rps": hi["goodput_rps"],
            "ratio": round(ratio, 2),
        }
        kill_cells = [c for c in cells if c["scenario"] == "kill_replica"]
        kill = (
            max(kill_cells, key=lambda c: c["replicas"])
            if kill_cells
            else None
        )
        acceptance = {
            "goodput_scaling_ratio": round(ratio, 2),
            "target_ratio": 3.0,
            "scaling_pass": ratio >= 3.0,
            "duplicates_total": duplicates_total,
            "duplicates_pass": duplicates_total == 0,
            "lost_total": lost_total,
            "lost_pass": lost_total == 0,
        }
        if kill is not None:
            acceptance["kill_ttft"] = {
                "replicas": kill["replicas"],
                "ttft_ms_p99": kill["ttft_ms_p99"],
                "bound_ms": kill["ttft_p99_bound_ms"],
                "pass": kill["ttft_p99_bounded"],
            }
        # Router-saturation bar: the sharded + shm-ring + batched path
        # must push the 4-replica saturation cell to >= 10x the
        # single-replica goodput of the standard (file-spool, single-
        # lane) healthy cell — the "memory-speed serve plane" claim.
        sat_ok = [c for c in sat_cells if c["scenario"] == "healthy"]
        if sat_ok and lo["goodput_rps"] > 0:
            sat_ratio = sat_ok[0]["goodput_rps"] / lo["goodput_rps"]
            comparisons["router_saturation"] = {
                "baseline_cell": lo["cell"],
                "baseline_goodput_rps": lo["goodput_rps"],
                "saturation_cell": sat_ok[0]["cell"],
                "saturation_goodput_rps": sat_ok[0]["goodput_rps"],
                "ratio": round(sat_ratio, 2),
            }
            acceptance["router_saturation_ratio"] = round(sat_ratio, 2)
            acceptance["router_saturation_target"] = 10.0
            acceptance["router_saturation_pass"] = sat_ratio >= 10.0
        acceptance["pass"] = (
            acceptance["scaling_pass"]
            and acceptance["duplicates_pass"]
            and acceptance["lost_pass"]
            and (kill is None or kill["ttft_p99_bounded"])
            and acceptance.get("router_saturation_pass", True)
        )

    result = {
        "bench": "serve_plane",
        "metric": "goodput_rps",
        "protocol": (
            "open-loop Poisson arrivals at a FIXED offered rate into the "
            "job's front spool; a real Supervisor (SubprocessRunner) "
            "spawns serve_stub engine replicas (slots concurrent "
            "requests, one token per slot per tpot_ms block — capacity "
            "= slots/(max_new_tokens*tpot_ms)); the supervisor-hosted "
            "router admission-controls against spec.serving.slo, "
            "dispatches least-loaded, re-routes on replica death, and "
            "publishes exactly-once. kill_replica SIGKILLs a replica "
            "mid-window through the runner; fail_engine_step aborts one "
            "decode block per replica from the env-threaded fault plan. "
            "Every submit is awaited: accounted == offered is the "
            "closure check, duplicates/lost are pinned 0, and the idle "
            "cell pins the router to zero work on a non-serving fleet."
        ),
        "cells": cells,
        "idle_overhead": idle,
        "comparisons": comparisons,
        "acceptance": acceptance,
    }
    if burn_cell is not None:
        result["burn_smoke"] = burn_cell
        comparisons["slo_burn_lifecycle"] = {
            "fired": burn_cell["burn_alert_fired"],
            "resolved": burn_cell["burn_alert_resolved"],
            "why_cites_slo_burn": burn_cell["why_cites_slo_burn"],
        }
    if overload_cell is not None:
        result["overload_remediation"] = overload_cell
        comparisons["overload_remediation"] = {
            "grows": overload_cell["remediation_grows"],
            "final_replicas": overload_cell["final_replicas"],
            "late_window_ok_rate": overload_cell["late_window_ok_rate"],
            "late_window_goodput_rps": overload_cell[
                "late_window_goodput_rps"
            ],
            "burn_resolved": overload_cell["burn_alert_resolved"],
            "recovered": overload_cell["recovered"],
        }
        if acceptance is not None:
            acceptance["remediation_recovery_pass"] = (
                overload_cell["recovered"]
                and overload_cell["burn_alert_resolved"]
            )
            acceptance["pass"] = (
                acceptance["pass"]
                and acceptance["remediation_recovery_pass"]
            )
    if out:
        Path(out).write_text(json.dumps(result, indent=2) + "\n")
        log(f"[serveplane] wrote {out}")
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--replicas",
        default="1,2,4",
        help="comma-separated replica counts per scenario",
    )
    p.add_argument(
        "--scenarios",
        default=",".join(SCENARIOS),
        help=f"comma-separated from {SCENARIOS}",
    )
    p.add_argument("--rate", type=float, default=85.0,
                   help="offered load, requests/s (open-loop Poisson)")
    p.add_argument("--duration", type=float, default=6.0,
                   help="arrival window per cell, seconds")
    p.add_argument("--slots", type=int, default=4,
                   help="concurrent slots per engine replica")
    p.add_argument("--tpot-ms", type=float, default=20.0,
                   help="simulated per-token decode time")
    p.add_argument("--max-new-tokens", type=int, default=8)
    p.add_argument("--max-queue-depth", type=int, default=32,
                   help="spec.serving.slo.max_queue_depth")
    p.add_argument("--deadline-s", type=float, default=2.0,
                   help="spec.serving.slo.deadline_s")
    p.add_argument("--retry-limit", type=int, default=2,
                   help="spec.serving.slo.retry_limit")
    p.add_argument("--idle-jobs", type=int, default=20,
                   help="non-serving jobs in the zero-overhead cell")
    p.add_argument("--idle-passes", type=int, default=30)
    p.add_argument(
        "--no-saturation",
        action="store_true",
        help="skip the router-saturation cells (shmring + sharded "
        "router at memory-speed offered load)",
    )
    p.add_argument(
        "--no-burn",
        action="store_true",
        help="skip the SLO burn-rate smoke cell (sustained overload "
        "driving the slo_burn alert through fire -> resolve)",
    )
    p.add_argument(
        "--no-remediation",
        action="store_true",
        help="skip the closed-loop overload cell (slo_burn fires, the "
        "remediation engine grows the fleet, goodput recovers)",
    )
    p.add_argument("--seed", type=int, default=7)
    p.add_argument(
        "--smoke",
        action="store_true",
        help="tiny under-capacity cells (healthy x {1,2}) — the tier-1 "
        "sanity shape, minutes -> seconds",
    )
    p.add_argument("--out", default=None, help="artifact path (JSON)")
    p.add_argument("--work-dir", default=None,
                   help="where the throwaway state dirs live")
    args = p.parse_args(argv)
    try:
        replicas = [int(x) for x in args.replicas.split(",") if x.strip()]
    except ValueError:
        print(f"--replicas must be comma-separated ints: {args.replicas!r}",
              file=sys.stderr)
        return 2
    scenarios = [s.strip() for s in args.scenarios.split(",") if s.strip()]
    bad = [s for s in scenarios if s not in SCENARIOS]
    if bad:
        print(f"unknown scenario(s) {bad}; choose from {SCENARIOS}",
              file=sys.stderr)
        return 2
    kwargs = dict(
        replica_cells=replicas,
        scenarios=scenarios,
        rate=args.rate,
        duration=args.duration,
        slots=args.slots,
        tpot_ms=args.tpot_ms,
        max_new_tokens=args.max_new_tokens,
        max_queue_depth=args.max_queue_depth,
        deadline_s=args.deadline_s,
        retry_limit=args.retry_limit,
        idle_jobs=args.idle_jobs,
        idle_passes=args.idle_passes,
        saturation=None if args.no_saturation else {},
        burn_smoke=not args.no_burn,
        overload_remediation=not args.no_remediation,
        seed=args.seed,
        out=args.out,
        work_dir=args.work_dir,
    )
    if args.smoke:
        kwargs.update(
            replica_cells=[1, 2],
            scenarios=["healthy"],
            rate=20.0,
            duration=1.5,
            tpot_ms=10.0,
            max_new_tokens=4,
            max_queue_depth=64,
            deadline_s=5.0,
            idle_timeout=2.5,
            idle_jobs=8,
            idle_passes=10,
            # The smoke saturation shape: 2 replicas, 2 shards, ring
            # path, mid-capacity rate — seconds, not minutes.
            saturation=None if args.no_saturation else {
                "replicas": 2,
                "scenarios": ("healthy", "kill_replica"),
                "rate": 120.0,
                "router_shards": 2,
            },
        )
    result = run(**kwargs)
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
