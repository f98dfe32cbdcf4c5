"""Worker-side rendezvous and status reporting.

Reference mapping (SURVEY.md §5 "Distributed communication backend"):

- ``dist.init_process_group('nccl', init_method='env://')`` reading
  MASTER_ADDR/PORT/RANK/WORLD_SIZE → :func:`initialize_from_env` reading the
  TPUJOB_* env the supervisor injected and calling
  ``jax.distributed.initialize(coordinator, num_processes, process_id)``.
- The reference's worker initContainer DNS-gate (``until nslookup
  $MASTER_ADDR``) → jax.distributed's built-in connect retry; we add an
  outer retry loop for coordinator-not-yet-listening races.
- DDP allreduce hooks over NCCL → XLA collectives over ICI/DCN, expressed
  via jax.sharding / shard_map in the workload (parallel/).

Workloads also report events (first step, per-step metrics) to
``$TPUJOB_STATUS_DIR`` as JSONL; the supervisor folds these into job status
(schedule-to-first-step latency, BASELINE.json:2).
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

from .backend import compile_counts


@dataclass
class WorldInfo:
    num_processes: int
    process_id: int
    coordinator: str
    replica_type: str
    replica_index: int
    restart_count: int
    job_key: str
    # Elastic resize epoch (controller/elastic.py): the generation of the
    # world this process belongs to. A resize record with a NEWER
    # generation in the status dir means the world moved on — poll_resize
    # yields either the process's place in the new world or its eviction.
    resize_generation: int = 0

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def world_from_env() -> WorldInfo:
    """Read the supervisor-injected cluster spec (SetClusterSpec analog)."""
    return WorldInfo(
        num_processes=int(os.environ.get("TPUJOB_NUM_PROCESSES", "1")),
        process_id=int(os.environ.get("TPUJOB_PROCESS_ID", "0")),
        coordinator=os.environ.get("TPUJOB_COORDINATOR_ADDRESS", "127.0.0.1:23456"),
        replica_type=os.environ.get("TPUJOB_REPLICA_TYPE", "Master"),
        replica_index=int(os.environ.get("TPUJOB_REPLICA_INDEX", "0")),
        restart_count=int(os.environ.get("TPUJOB_RESTART_COUNT", "0")),
        job_key=os.environ.get("TPUJOB_KEY", "default/local"),
        resize_generation=int(os.environ.get("TPUJOB_RESIZE_GENERATION", "0")),
    )


def fault_stall_if_armed() -> float:
    """The ``stall_rendezvous`` injection site: sleep (and report) the
    seconds an armed fault plan asks for, returning them. A no-op
    (0.0, no imports beyond the light faults package) without a plan.

    Public because workloads that never reach jax.distributed (e.g. the
    single-process ``exit_with`` chaos casualty) call it directly to
    model a slow join on the same code path."""
    from .. import faults

    seconds = faults.rendezvous_stall_seconds()
    if seconds > 0:
        report("fault_stall", seconds=seconds, site="rendezvous")
        time.sleep(seconds)
    return seconds


def join_backoff(timeout_s: float, base_s: float, seed: int):
    """The rendezvous retry schedule: exponential + deterministic jitter
    (seeded per process id so a gang's workers decorrelate instead of
    herding on the coordinator every fixed 1 s), capped well inside the
    join timeout so late attempts still fit."""
    from ..backoff import Backoff

    return Backoff(
        base_s=base_s,
        cap_s=max(base_s, min(10.0, timeout_s / 4.0)),
        jitter=0.25,
        seed=seed,
    )


# ---- elastic resize (controller/elastic.py is the writer) ----


@dataclass
class ResizeSignal:
    """One observed resize-record advance: either this process's place in
    the new world, or its eviction from it."""

    generation: int
    evicted: bool
    world: Optional[WorldInfo]  # None when evicted
    restore_step: Optional[int]  # last sidecar-verified step at resize time
    record: dict


def _member_id(world: WorldInfo) -> str:
    return f"{world.replica_type.lower()}-{world.replica_index}"


def read_resize_record() -> Optional[dict]:
    d = os.environ.get("TPUJOB_STATUS_DIR")
    if not d:
        return None
    try:
        with open(Path(d) / "resize.json") as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def poll_resize(world: WorldInfo) -> Optional[ResizeSignal]:
    """Step-loop resize check: has the supervisor advanced the world past
    this process's generation? One stat+read per call, nothing without a
    status dir. Returns None while the world is current; otherwise a
    signal carrying the new membership — or the eviction fence: a
    process absent from the record's rank map has NO place in the new
    world and must exit rather than join (the stale-generation-straggler
    guard)."""
    rec = read_resize_record()
    if rec is None:
        return None
    try:
        gen = int(rec.get("generation", 0))
    except (TypeError, ValueError):
        return None
    if gen <= world.resize_generation:
        return None
    ranks = rec.get("ranks") or {}
    restore = rec.get("restore_step")
    restore = int(restore) if restore is not None else None
    rank = ranks.get(_member_id(world))
    if rank is None:
        return ResizeSignal(gen, True, None, restore, rec)
    from dataclasses import replace

    new_world = replace(
        world,
        num_processes=int(rec.get("world_size", len(ranks))),
        process_id=int(rank),
        coordinator=str(rec.get("coordinator", world.coordinator)),
        resize_generation=gen,
    )
    return ResizeSignal(gen, False, new_world, restore, rec)


def adopt_resize(sig: ResizeSignal) -> WorldInfo:
    """Become a member of the resized world (jax-free path: the caller's
    step loop keeps running with the returned WorldInfo). Reports the
    re-join on the status channel — `tpujob why`'s resize history and
    the bench's duplicate-rank check both read these records."""
    report(
        "resize_join",
        generation=sig.generation,
        rank=sig.world.process_id,
        world_size=sig.world.num_processes,
    )
    return sig.world


def exit_for_resize(sig: ResizeSignal) -> None:
    """Terminal resize outcomes. Evicted: report and exit 0 — this
    process has no rank in the new world (fenced out). Member of a REAL
    jax.distributed world: re-exec in place — same pid, same log file,
    no scheduler round trip — with the environment rewritten to the new
    generation's coordinates; the fresh ``main()`` re-joins at the new
    coordinator and restores from the last verified checkpoint. (In-
    process jax.distributed re-initialization is not reliably supported;
    exec is the surgical alternative to a gang teardown.)"""
    import sys

    if sig.evicted:
        report("resize_evicted", generation=sig.generation)
        print(
            f"[rendezvous] evicted by resize generation {sig.generation}; "
            "exiting.",
            flush=True,
        )
        sys.stdout.flush()
        sys.stderr.flush()
        raise SystemExit(0)
    w = sig.world
    host, _, port = w.coordinator.rpartition(":")
    os.environ.update(
        {
            "TPUJOB_NUM_PROCESSES": str(w.num_processes),
            "TPUJOB_PROCESS_ID": str(w.process_id),
            "TPUJOB_COORDINATOR_ADDRESS": w.coordinator,
            "TPUJOB_RESIZE_GENERATION": str(w.resize_generation),
            "WORLD_SIZE": str(w.num_processes),
            "RANK": str(w.process_id),
            "MASTER_ADDR": host or "127.0.0.1",
            "MASTER_PORT": port,
        }
    )
    report(
        "resize_join",
        generation=sig.generation,
        rank=w.process_id,
        world_size=w.num_processes,
        via="exec",
    )
    print(
        f"[rendezvous] re-joining resized world: generation "
        f"{sig.generation}, rank {w.process_id}/{w.num_processes} "
        f"at {w.coordinator} (in-place exec)",
        flush=True,
    )
    sys.stdout.flush()
    sys.stderr.flush()
    argv = getattr(sys, "orig_argv", None)
    if argv and len(argv) > 1:
        os.execv(sys.executable, [sys.executable] + list(argv[1:]))
    os.execv(sys.executable, [sys.executable] + sys.argv)


def initialize_from_env(
    timeout_s: float = 60.0, retry_interval_s: float = 1.0
) -> WorldInfo:
    """Join the jax.distributed world described by the environment.

    Single-process worlds skip initialization entirely (single-process SPMD
    across local devices). Multi-process worlds call
    ``jax.distributed.initialize`` with retries — the connect-retry gate
    that replaces the reference's initContainer DNS loop, now on a
    jittered exponential backoff (``retry_interval_s`` is the base
    delay); the outer ``timeout_s`` contract is unchanged.
    """
    from .backend import setup_backend
    from .. import obs

    t_join = time.time()
    fault_stall_if_armed()
    setup_backend()
    world = world_from_env()
    # Resize fence: an environment stamped with an older generation than
    # the status dir's resize record describes a world that no longer
    # exists. A straggler still named in the new member map adopts its
    # new coordinates BEFORE the first join (a promoted spare or a
    # replica recreated mid-failover lands here); one absent from the
    # map is fenced out and exits cleanly — it must not camp on the old
    # coordinator port waiting for a gang that will never assemble.
    sig = poll_resize(world)
    if sig is not None:
        if sig.evicted:
            exit_for_resize(sig)
        world = adopt_resize(sig)
    if world.num_processes <= 1:
        return world

    import jax

    from ..backoff import retry_call

    def join():
        jax.distributed.initialize(
            coordinator_address=world.coordinator,
            num_processes=world.num_processes,
            process_id=world.process_id,
        )

    try:
        with obs.span(
            "rendezvous_join", cat="rendezvous",
            coordinator=world.coordinator, world=world.num_processes,
        ):
            retry_call(
                join,
                backoff=join_backoff(
                    timeout_s, retry_interval_s, world.process_id
                ),
                timeout_s=timeout_s,
            )
        # Join latency rides the status channel into the supervisor's
        # /metrics histogram (the supervisor cannot time a join it does
        # not perform).
        report("rendezvous_join", seconds=time.time() - t_join)
        return world
    except Exception as e:  # pragma: no cover - env-dependent errors
        raise TimeoutError(
            f"rendezvous with coordinator {world.coordinator} failed after "
            f"{timeout_s}s: {e}"
        ) from e


def finalize(world: WorldInfo, exit_code: int = 0) -> None:
    """Leave a multi-process world deterministically after the workload
    finished: coordination-service barrier, leader grace, hard
    ``os._exit``.

    The hard exit is the point. jax's implicit atexit teardown races
    its own gloo/coordination threads and intermittently segfaults a
    replica that COMPLETED all its work — and a 139 is retryable, so
    every such exit burns a restart and re-runs a finished life. A
    replica that reached finalize owes nothing to interpreter teardown;
    flush and leave. Single-process worlds (nothing was initialized)
    return normally so in-process callers (unit tests) survive.

    The barrier is the coordination service's key-value barrier (pure
    RPC), NOT a jax collective — multi-process collectives are backend-
    dependent (unimplemented on CPU) and ``jax.distributed.shutdown``
    itself is part of the teardown being avoided. After the barrier,
    every peer is provably done; non-leaders exit immediately, and the
    leader lingers one beat so the coordination service it hosts stays
    up while they leave (a leader that vanishes first turns its peers'
    clean exits into "leader task died" aborts).

    A barrier failure is swallowed: it means a PEER died, and that is
    the supervisor's problem — this replica's work is done and its exit
    code must say so.
    """
    if world.num_processes <= 1:
        return
    sys.stdout.flush()
    sys.stderr.flush()
    try:
        from jax._src import distributed

        client = distributed.global_state.client
        if client is not None:
            try:
                client.wait_at_barrier("tpujob_finalize", 10_000)
            except Exception:
                # invariant: waived — finalize barrier is best-effort; peers may already be gone at exit
                pass
            if world.process_id == 0:
                time.sleep(1.0)
    except Exception:
        # invariant: waived — nothing may stop the resize exit code from reaching the supervisor via os._exit
        pass
    os._exit(exit_code)


# ---- status reporting (workload → supervisor) ----


def _status_path() -> Optional[Path]:
    d = os.environ.get("TPUJOB_STATUS_DIR")
    if not d:
        return None
    rtype = os.environ.get("TPUJOB_REPLICA_TYPE", "Master").lower()
    idx = os.environ.get("TPUJOB_REPLICA_INDEX", "0")
    return Path(d) / f"{rtype}-{idx}.jsonl"


def report(event: str, **fields) -> None:
    """Append a status record; no-op when not running under the supervisor."""
    path = _status_path()
    if path is None:
        return
    rec = {"event": event, "ts": time.time(), **fields}
    try:
        with path.open("a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError:
        pass


def progress_enabled() -> bool:
    """Is anyone listening? Workloads gate their heartbeat on this so a
    standalone benchmark run (no supervisor, no status dir) pays zero
    telemetry fences and stays A/B-comparable with older numbers."""
    return _status_path() is not None


# The last supervisor clock-probe seq this process echoed (each probe
# is answered exactly once — a re-echo would hand the estimator a
# stale round trip whose [probe, observe] interval spans seconds).
_probe_echoed_seq: Optional[int] = None


def _maybe_echo_probe() -> None:
    """Echo the supervisor's round-trip clock probe (obs/clock.py):
    read ``clock_probe.json`` from the status dir and, for a probe not
    yet answered, append a ``clock_probe`` record whose own ``ts`` is
    this replica's send time — the (probe write, echo send, echo
    observe) triple lets the offset estimator cancel the one-way delay
    bias. Piggybacks on the heartbeat cadence: one stat+read per beat,
    nothing without a supervisor."""
    global _probe_echoed_seq
    d = os.environ.get("TPUJOB_STATUS_DIR")
    if not d:
        return
    from ..obs.clock import read_probe

    probe = read_probe(d)
    if probe is None or probe["seq"] == _probe_echoed_seq:
        return
    _probe_echoed_seq = probe["seq"]
    report("clock_probe", probe_ts=probe["probe_ts"], seq=probe["seq"])


def report_device() -> dict:
    """Put this replica's :func:`~.backend.device_report` on the status
    channel (a ``device`` record per replica — what a gang's processes
    each saw) and return it for the workload's own result."""
    from .backend import device_report

    dev = device_report()
    report("device", **dev)
    return dev


def report_first_step(step: int = 0) -> None:
    """The replica is up; with it, how many programs it had to compile to
    get there and how many the compile cache held (a warm start: 0 and
    all of them; ``tpujob why`` prints both)."""
    report("first_step", step=step, **compile_counts())


def report_metrics(step: int, **metrics) -> None:
    report("metrics", step=step, **compile_counts(), **metrics)


def report_progress(
    step: int,
    *,
    loss: Optional[float] = None,
    steps_per_sec: Optional[float] = None,
    throughput: Optional[float] = None,
    unit: Optional[str] = None,
    step_time_ms: Optional[float] = None,
    feed_stall_ms: Optional[float] = None,
) -> None:
    """Live training heartbeat (step/loss/throughput) for the operator
    surface: the supervisor folds the newest record into per-job
    /metrics gauges and ``tpujob describe``'s "Training" block
    (controller/progress.py). Emit every ~10s, not every step — each
    record is a host write and the caller usually pays a device fence
    to know the loss."""
    # ``drop_heartbeat`` injection site: an armed fault plan can
    # suppress heartbeats to trip the supervisor's hung-world detector
    # (controller/reconciler.py). No-op without a plan.
    from .. import faults, obs

    # The beat is a write already: the spans buffered since the last one
    # go out with it (obs/trace.py buffers; a kill loses only this tail).
    obs.flush()
    if faults.heartbeat_dropped():
        return
    fields = {}
    if loss is not None:
        fields["loss"] = round(float(loss), 6)
    if steps_per_sec is not None:
        fields["steps_per_sec"] = round(float(steps_per_sec), 4)
    if throughput is not None:
        fields["throughput"] = round(float(throughput), 4)
    if unit is not None:
        fields["unit"] = unit
    if step_time_ms is not None:
        fields["step_time_ms"] = round(float(step_time_ms), 3)
    if feed_stall_ms is not None:
        fields["feed_stall_ms"] = round(float(feed_stall_ms), 3)
    report("progress", step=step, **fields)
    # Round-trip clock probe: answered on the heartbeat cadence, AFTER
    # the beat (the supervisor probes jobs it just saw beating).
    _maybe_echo_probe()


def report_serve(
    requests: int,
    *,
    slots: int,
    slots_free: int,
    queued: int = 0,
    pending: int = 0,
    ttft_ms_p50: Optional[float] = None,
    ttft_ms_p99: Optional[float] = None,
    tpot_ms_p50: Optional[float] = None,
    tpot_ms_p99: Optional[float] = None,
    block_ms: Optional[float] = None,
) -> None:
    """Serve-plane load beat: slot occupancy, queue depth, and latency
    percentiles for this engine replica. The supervisor's router
    (serving/router.py) reads the newest record per replica from the
    heartbeat fold — zero extra I/O — to score least-loaded dispatch,
    and the queue_growth / batch_size_collapse detectors judge the same
    stream. Emit on the serve loop's report cadence, like progress."""
    fields: dict = {
        "slots": int(slots),
        "slots_free": int(slots_free),
        "queued": int(queued),
        "pending": int(pending),
    }
    for k, v in (
        ("ttft_ms_p50", ttft_ms_p50),
        ("ttft_ms_p99", ttft_ms_p99),
        ("tpot_ms_p50", tpot_ms_p50),
        ("tpot_ms_p99", tpot_ms_p99),
        # Decode-block phase: ms until the engine's current decode
        # block completes and a batch slot can actually be filled —
        # the router's continuous-batching dispatch tie-breaker.
        ("block_ms", block_ms),
    ):
        if v is not None:
            fields[k] = round(float(v), 3)
    report("serve", requests=int(requests), **fields)


def report_checkpoint_committed(
    step: int,
    commit_s: float,
    queue_depth: int = 0,
    oldest_age_s: float = 0.0,
    stage_depth: int = 0,
) -> None:
    """Async-checkpoint commit telemetry for the operator surface: the
    supervisor folds the newest record into the per-job checkpoint-step
    /queue-depth/oldest-inflight-age/stage-depth gauges and observes
    the commit duration into ``tpujob_checkpoint_commit_seconds`` —
    checkpoint lag in ``tpujob top`` is ``job_step -
    job_checkpoint_step``. ``stage_depth`` counts submitted saves whose
    device→host gather has not finished (the staged writer's snapshot
    stage — a growing value means gathers cannot keep up with the save
    cadence)."""
    report(
        "checkpoint_committed",
        step=step,
        commit_ms=round(1000.0 * commit_s, 3),
        queue_depth=int(queue_depth),
        oldest_age_s=round(oldest_age_s, 3),
        stage_depth=int(stage_depth),
    )
