"""The job supervisor — operator main loop.

Reference: ``cmd/pytorch-operator.v1`` + ``controller.Run(threadiness,
stopCh)`` (SURVEY.md §3.1): wire stores/recorders/reconciler, then loop
reconcile passes until jobs finish. Also owns TTL garbage collection and
elastic resize (scale) requests.
"""

from __future__ import annotations

import os
import socket
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import List, Optional

from ..api.defaults import (
    AUTO_PORT_ANNOTATION,
    ELASTIC_TARGET_ANNOTATION,
    HANG_DEADLINE_ANNOTATION,
    set_defaults,
)
from ..api.types import ConditionType, ReplicaType, TPUJob
from ..api.validation import ValidationError, validate
from .autoscale import PoolAutoscaler
from .events import EventRecorder
from .expectations import ControllerExpectations
from .gang import GangScheduler
from .leases import SHARD_EVENT_KEY, LeaderLease, ShardManager, default_identity
from .metrics import MetricsRegistry
from .progress import ProgressTailer, job_status_dir
from .reconciler import Reconciler
from .runner import ProcessRunner, SubprocessRunner, replica_name
from .store import JobStore, job_key, purge_job_artifacts


class SupervisorKilledError(RuntimeError):
    """Raised by :meth:`Supervisor.simulate_crash` — the in-process
    stand-in for an abrupt daemon death (``kill_supervisor`` fault in
    tests/benches; a real daemon just ``os._exit``\\ s)."""


def default_state_dir() -> Path:
    return Path(os.environ.get("TPUJOB_HOME", ".tpujob"))


def _find_free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Supervisor:
    def __init__(
        self,
        state_dir: Optional[Path] = None,
        runner: Optional[ProcessRunner] = None,
        gang_enabled: bool = True,
        max_slots: Optional[int] = None,
        poll_interval: float = 0.1,
        persist: bool = True,
        leader_elect: bool = False,
        queue_slots: Optional[dict] = None,
        preempt: bool = False,
        standby: int = 0,
        parallel_sync: bool = True,
        sync_workers: Optional[int] = None,
        cached_store: bool = True,
        shards: Optional[int] = None,
        supervisor_id: Optional[str] = None,
        lease_ttl: float = 5.0,
        sync_workers_max: Optional[int] = None,
    ):
        self.state_dir = Path(state_dir) if state_dir is not None else default_state_dir()
        self.state_dir.mkdir(parents=True, exist_ok=True)
        self.identity = supervisor_id or default_identity()
        # Sharded control plane (``--shards N``): job-space partitioned
        # across N store-marker leases; this supervisor reconciles only
        # the shards it holds. Replaces leader election — the whole
        # point is MULTIPLE active reconcilers on one state dir.
        self.shards = (
            ShardManager(
                self.state_dir, shards, identity=self.identity, ttl=lease_ttl
            )
            if shards
            else None
        )
        # Leader election (reference: leaderelection.RunOrDie, SURVEY.md §3.1).
        # The lease is created here but acquired by the daemon entrypoint, so
        # library users (tests, foreground run) aren't serialized by default.
        # Single-supervisor semantics are exactly ShardManager(num_shards=1)
        # — kept as-is so existing daemons/tests run unchanged.
        self.lease = (
            LeaderLease(self.state_dir)
            if leader_elect and self.shards is None
            else None
        )
        self.poll_interval = poll_interval
        # Events before the store: persistence-layer warnings (corrupt
        # state files skipped at load, stale tmp sweeps) land on the
        # event surface `tpujob describe` reads.
        self.events = EventRecorder(sink_dir=self.state_dir / "events")
        # cached_store=False reproduces the pre-cache store I/O profile —
        # only the control-plane bench should ever ask for it.
        self.store = JobStore(
            persist_dir=self.state_dir / "jobs" if persist else None,
            events=self.events,
            cache=cached_store,
        )
        # Parallel reconcile phase (reference: controller.Run(threadiness)
        # — the workqueue's N workers): steady-state jobs sync on a
        # thread pool whose size a latency-driven autoscaler controls
        # (controller/autoscale.py) against the measured steady-phase
        # latency, bounded by --sync-workers-max. An EXPLICIT
        # sync_workers with no ceiling pins the old fixed-size behavior.
        self.parallel_sync = parallel_sync
        base = sync_workers or min(8, os.cpu_count() or 2)
        if sync_workers is not None and sync_workers_max is None:
            floor = ceiling = base  # explicitly pinned: no autoscaling
        else:
            ceiling = sync_workers_max or base
            floor = min(2, ceiling)
        self._pool_scaler = PoolAutoscaler(floor=floor, ceiling=ceiling)
        self._sync_workers = self._pool_scaler.size
        self._sync_pool = None
        self._sync_pool_size = 0
        self._sync_pool_lock = threading.Lock()
        # Incremental heartbeat reader for the per-job training gauges:
        # remembers a byte offset per replica status file, so an idle
        # pass costs one directory scan per job and zero reads.
        self._progress = ProgressTailer()
        # Supervisor pass counter for the fault-injection pass hook
        # (kill_replica faults schedule against it).
        self._fault_pass = 0
        # kill_supervisor fault behavior: None = real daemon death
        # (os._exit); tests/benches set simulate_crash to keep the
        # process alive while THIS supervisor stops cold.
        self.fault_kill_action = None
        # Steady fast path: key -> job.generation recorded after a full
        # steady-phase reconcile found nothing to do. A later pass may
        # skip the full reconcile iff the generation still matches AND
        # the runner reported no replica change AND the status files
        # grew no new bytes — at 10k jobs this is what keeps the idle
        # pass flat instead of O(jobs × reconcile machinery).
        self._steady_gen: dict = {}
        # Companion cache for the scheduling-phase classifier: key ->
        # generation at which _needs_scheduling last returned False.
        # Valid under the same invariants (generation + runner change
        # set), with the two fields callers may legally flip WITHOUT
        # touch() — run_policy.suspend and elastic_policy — re-checked
        # live in the gate.
        self._steady_ok: dict = {}
        # Per-pass stash of tailer polls done by the fast-path gate, so
        # the gauge fold does not scan the same status dir twice.
        self._pass_polled: dict = {}
        # Jobs whose status dir held NO replica files at the last poll
        # (never reported): re-scanned only every 4th pass, staggered by
        # key hash — a 10k-job idle fleet must not pay 10k scandirs per
        # pass for directories that are provably empty. key -> stagger.
        self._dir_empty: dict = {}
        self._pass_no = 0
        # Keys fast-skipped THIS pass (provably unchanged): the gauge
        # fold reuses the pass loop's is_finished verdict for them.
        self._pass_fast_skipped: set = set()
        # key -> shard id (hash or spec pin), cached: the per-pass
        # ownership filter must cost a dict lookup, not a spec walk.
        self._shard_cache: dict = {}
        self.metrics = MetricsRegistry()
        self.runner = runner if runner is not None else SubprocessRunner(
            self.state_dir, max_slots=max_slots, standby=standby
        )
        # Warm-standby sizing: the operator's --standby is the floor; the
        # max elastic_policy.hot_spares across unfinished elastic jobs
        # raises it per pass (set_standby_target is called only on change).
        self._standby_base = max(0, int(standby))
        self._standby_want = self._standby_base
        self.gang = GangScheduler(enabled=gang_enabled)
        # volcano `preempt` action analog; opt-in (--preempt).
        self.preempt_enabled = preempt
        self.expectations = ControllerExpectations()
        self.reconciler = Reconciler(
            store=self.store,
            runner=self.runner,
            events=self.events,
            metrics=self.metrics,
            gang=self.gang,
            expectations=self.expectations,
            status_root=self.state_dir / "status",
            checkpoint_root=self.state_dir / "checkpoints",
            queue_slots=queue_slots,
            trace_root=self.state_dir / "trace",
            serve_root=self.state_dir / "serve",
        )
        # Flight-recorder wiring (obs/): the store times its own
        # persist/rescan into these histograms, and the per-pass counter
        # folds below mirror the bench-only I/O instrumentation onto the
        # live /metrics. Last-seen snapshots make the counter folds
        # delta-based (counters are monotonic; the sources are too).
        self.store.persist_hist = self.metrics.store_persist_seconds
        self.store.rescan_hist = self.metrics.store_rescan_seconds
        self._store_io_seen = self.store.io.snapshot()
        self._progress_io_seen = self._progress.io.snapshot()
        # Per-job ts of the newest folded heartbeat / checkpoint record:
        # histograms must observe each record ONCE, not once per pass.
        self._hb_observed: dict = {}
        self._ckpt_observed: dict = {}
        # Clock-observation fold (obs/clock.py): per-(key, replica) ts of
        # the newest beat already paired with a supervisor observe time,
        # and one append-only log per job. First sight of a replica only
        # PRIMES the dedup — a daemon restart must not pair a stale beat
        # with a fresh observe time (a garbage delay sample).
        self._clock_logs: dict = {}
        self._clock_seen: dict = {}
        # Round-trip clock probes: per-key ts of the last probe file
        # write (cadence gate), the recent probe seqs THIS supervisor
        # wrote per key (only their echoes are accepted — a stale echo
        # after a daemon restart would be a garbage round trip), and
        # per-(key, replica) ts of the newest echo already logged.
        self._probe_written: dict = {}
        self._probe_seqs: dict = {}
        self._probe_seen: dict = {}
        # The live health engine (obs/watch.py): streaming detector
        # rules + alert lifecycle, fed from the SAME tailed state as
        # the gauge fold — zero extra I/O; log appends only on alert
        # transitions.
        from ..obs.watch import WatchEngine

        self.watch = WatchEngine(self.state_dir)
        # Serve plane (serving/router.py): the request router for
        # spec.serving jobs, ticked from the gauge fold. Jobs without a
        # serving block never reach it — one ``is None`` check per job
        # per pass, no extra I/O, <state>/serve never created (the
        # bench_smoke zero-overhead pin).
        from ..serving.router import ServeRouter

        self.router = ServeRouter(self.state_dir, metrics=self.metrics)
        self._router_io_seen = self.router.io_snapshot()
        self._router_lane_seen: dict = {}
        # Auto-remediation (controller/remediation.py): consumes the
        # watch engine's firing alerts + the router's serve summary,
        # right after both, on the same pass thread. Jobs without a
        # spec.remediation block never reach it — one ``is None`` check
        # per job per pass, zero I/O until something fires.
        from .remediation import RemediationEngine

        self.remediation = RemediationEngine(
            self.state_dir, self.store, self.runner, self.reconciler,
            self.events, self.metrics,
        )
        self.remediation.fence_for = self._remediation_fence
        # Serving jobs whose end-of-life drain already ran (the drain
        # scans the front spool — once, not every pass).
        self._serve_finalized: set = set()
        if self.shards is not None:
            # Markers are consumed by rename-claim (exactly-once): a
            # sharded supervisor must not claim one for a job another
            # shard owner reconciles.
            self.store.key_filter = self._owns_key

    # ---- sharded control plane ----

    def _job_shard(self, key: str) -> int:
        """The job's shard (hash of key, or the spec's explicit pin),
        cached per key — the ownership filter runs per job per pass."""
        s = self._shard_cache.get(key)
        if s is None:
            job = self.store.get(key)
            pin = None
            if job is not None:
                pin = job.spec.run_policy.scheduling_policy.shard
            s = self.shards.shard_of(key, pin)
            if job is not None:
                self._shard_cache[key] = s
        return s

    def _owns_key(self, key: str, now: Optional[float] = None) -> bool:
        return self.shards.owns_shard(self._job_shard(key), now)

    def _remediation_fence(self, key: str) -> Optional[dict]:
        """The fencing coordinates a remediation audit record carries:
        which shard lease (and token epoch) authorized the commit.
        None unsharded — the store is single-writer by construction."""
        if self.shards is None:
            return None
        s = self._job_shard(key)
        lease = self.shards.leases.get(s)
        return {
            "shard": s,
            "token": lease.token if lease is not None else 0,
            "holder": self.identity,
        }

    def _shard_tick(self, now: float) -> dict:
        """Once per pass: renew/claim/release shard leases, then turn
        the changes into state the rest of the pass relies on — adopt
        replica records of acquired shards, reload their (possibly
        stale) job objects, forget what was handed off — and record
        every hand-off on the shared shard event log so ``tpujob why``
        can cite an ownership change."""
        changes = self.shards.tick(now)
        m = self.metrics
        for i in changes["lost"]:
            m.shard_losses.inc()
            self.events.warning(
                SHARD_EVENT_KEY,
                "ShardLeaseLost",
                f"shard {i} lease lost by {self.identity} "
                "(fencing rejection or expiry before renewal).",
            )
            self._drop_shard_state(i)
        for i in changes["released"]:
            m.shard_releases.inc()
            self.events.normal(
                SHARD_EVENT_KEY,
                "ShardReleased",
                f"shard {i} released by {self.identity} (rebalance to "
                f"{changes['members']} supervisors).",
            )
            self._drop_shard_state(i)
        if changes["acquired"]:
            owned_now = set(changes["acquired"])
            # Adopt the replica records (and live processes) the
            # previous owner left behind — only for shards now ours.
            self.runner.rescan(
                key_filter=lambda k: self._job_shard(k) in owned_now
            )
            for i in changes["acquired"]:
                m.shard_acquisitions.inc()
                lease = self.shards.leases[i]
                msg = (
                    f"shard {i} acquired by {self.identity} "
                    f"(token {lease.token})"
                )
                if lease.takeover_from:
                    # Stolen after expiry: the previous holder stopped
                    # renewing — died, hung, or was partitioned.
                    msg += f" after lease expiry of {lease.takeover_from}"
                self.events.normal(SHARD_EVENT_KEY, "ShardAcquired", msg + ".")
            # Our cached job objects for these shards may be stale (the
            # previous owner mutated them up to its death/release).
            for key in self.store.keys():
                if self._job_shard(key) in owned_now:
                    self.store.reload(key)
                    self._steady_gen.pop(key, None)
                    self._steady_ok.pop(key, None)
        return changes

    def _drop_shard_state(self, shard_id: int) -> None:
        """Hand-off bookkeeping for a shard we no longer own: stop
        tracking its replicas (processes/records stay for the adopter),
        drop fast-path and health-engine state, retire its metric
        series from THIS supervisor's registry."""
        for key in self.store.keys():
            if self._job_shard(key) == shard_id:
                self.runner.forget_job(key)
                self._steady_gen.pop(key, None)
                self._steady_ok.pop(key, None)
                self.watch.retire_job(key)
                self.remediation.retire_job(key)
                self.metrics.retire_job(key)

    def simulate_crash(self) -> None:
        """In-process stand-in for an abrupt daemon death (the
        ``kill_supervisor`` fault in tests/benches): stop cold without
        releasing leases or killing replicas — survivors must win the
        shards back by EXPIRY, exactly like a real SIGKILL. The renewal
        thread is halted (a dead process renews nothing)."""
        if self.shards is not None:
            self.shards.halt()
        raise SupervisorKilledError(self.identity)

    # ---- API-server-ish surface ----

    def submit(self, job: TPUJob) -> str:
        """Accept a job: default, validate, store (kubectl-apply analog).

        Omitted ports are marked auto by set_defaults; the reconciler probes
        a free port right before each world launch.
        """
        set_defaults(job)
        validate(job)
        key = job_key(job)
        # A previous incarnation deleted cross-process (`tpujob delete`
        # with no daemon running) removes the STORE record immediately but
        # leaves replica records/processes — and the marker — for the
        # consumer, which may be this very supervisor. Reap stale state
        # through the canonical teardown before accepting the new
        # incarnation: adopting a stale finished master's exit record
        # would complete the new job without ever running it. The marker
        # clear is unconditional: a surviving marker would make a later
        # daemon delete the NEW incarnation mid-run.
        if self.store.get(key) is None:
            if self.runner.list_for_job(key):
                # Honor the orphaned marker's purge request (the user's
                # `delete --purge` must not leave a checkpoint the new
                # incarnation silently resumes from).
                self.delete_job(
                    key, purge_artifacts=self.store.marker_requests_purge(key)
                )
            self.store.clear_deletion_marker(key)
        key = self.store.add(job)
        self.events.normal(key, "TPUJobSubmitted", f"TPUJob {key} accepted.")
        return key

    def get(self, key: str) -> Optional[TPUJob]:
        return self.store.get(key)

    def list_jobs(self) -> List[TPUJob]:
        return self.store.list()

    def delete_job(self, key: str, purge_artifacts: bool = False) -> bool:
        """Delete a job and terminate its replicas (kubectl delete analog).

        Checkpoints/status artifacts survive by default (job-level resume,
        SURVEY.md §5); ``purge_artifacts=True`` reclaims them.
        """
        # Serialize against an in-flight sync of this job: a teardown that
        # interleaves with a reconcile pass would race replica creation.
        with self.reconciler.key_lock(key):
            job = self.store.get(key)
            # Replica processes/records can outlive the store record (a
            # cross-process `tpujob delete` removes the record up front
            # and leaves the reaping to the marker consumer) — the full
            # teardown runs regardless, so the daemon's marker-driven
            # delete can't leak events/locks/gang state per key.
            self.runner.delete_many(
                [h.name for h in self.runner.list_for_job(key)]
            )
            self.gang.delete_group(key)
            self.expectations.delete_expectations(key)
            self.reconciler.prune_crash_backoff(key)
            if job is not None:
                self.store.delete(key)
            self.events.drop_job(key)
            self._retire_job_telemetry(key)
            if purge_artifacts:
                purge_job_artifacts(self.state_dir, key)
        # NOTE: the key's reconcile lock is NOT dropped here — delete_job
        # now runs nested under callers that hold it (apply→submit's
        # stale reap, the daemon's marker loop), and popping a held RLock
        # would let a concurrent sync mint a fresh one and race the
        # holder. Long-running daemons GC retired locks instead
        # (Reconciler.gc_key_locks, called from the daemon loop).
        return job is not None

    def apply(self, job: TPUJob) -> str:
        """kubectl-apply semantics: create the job if absent, update the
        spec in place if active, or start a fresh incarnation if finished.

        An active job whose WORLD SHAPE changed (replica specs or port)
        gets a gang restart at the new shape — the pod-template-change
        semantics; run-policy-only changes (TTL, deadline, scheduling,
        suspend) take effect without touching the running world.
        """
        set_defaults(job)
        validate(job)
        key = job_key(job)
        with self.reconciler.key_lock(key):
            cur = self.store.get(key)
            if cur is None:
                return self.submit(job)
            if cur.is_finished():
                # Fresh incarnation: the old record (and its terminal
                # status) is replaced; checkpoints/artifacts survive, as
                # on resubmission.
                self.runner.delete_many(
                    [h.name for h in self.runner.list_for_job(key)]
                )
                self.store.delete(key)
                self.events.normal(
                    key, "TPUJobReplaced", "finished job replaced by apply."
                )
                return self.submit(job)
            # Auto-port jobs carry a freshly-probed port per world launch;
            # comparing those would flag every apply as a world change.
            both_auto = (
                cur.metadata.annotations.get(AUTO_PORT_ANNOTATION) == "true"
                and job.metadata.annotations.get(AUTO_PORT_ANNOTATION) == "true"
            )
            world_changed = cur.spec.replica_specs != job.spec.replica_specs or (
                not both_auto and cur.spec.port != job.spec.port
            )
            if both_auto:
                job.spec.port = cur.spec.port  # keep the live probed port
            cur.spec = job.spec
            cur.touch()
            # The spec may carry a new explicit shard pin.
            self._shard_cache.pop(key, None)
            # New metadata wins; system identity (uid/creation/submit) stays.
            cur.metadata.labels.update(job.metadata.labels)
            cur.metadata.annotations.update(job.metadata.annotations)
            if job.metadata.annotations.get(AUTO_PORT_ANNOTATION) != "true":
                # The incoming spec pinned an explicit port: drop the stale
                # auto-port marker or the reconciler would re-probe a
                # random port at relaunch and ignore the user's choice.
                cur.metadata.annotations.pop(AUTO_PORT_ANNOTATION, None)
            if job.spec.elastic_policy is not None:
                workers = job.spec.replica_specs.get(ReplicaType.WORKER)
                if workers is not None:
                    # Apply re-pins the grow-back target like manual scale.
                    cur.metadata.annotations[ELASTIC_TARGET_ANNOTATION] = str(
                        workers.replicas
                    )
            handles = self.runner.list_for_job(key)
            if world_changed and handles:
                msg = (
                    f"spec update changed the world shape "
                    f"(restart #{cur.status.restart_count + 1})."
                )
                self.reconciler.restart_world(
                    cur, key, handles, "TPUJobUpdated", msg, warning=False
                )
            else:
                self.events.normal(
                    key, "TPUJobUpdated", "spec updated in place."
                )
            self.store.update(cur)
            return key

    def process_apply_markers(self) -> None:
        """Act on cross-process ``tpujob apply`` requests."""
        from ..api.serialization import job_from_dict

        for key, job_dict in self.store.take_apply_markers():
            try:
                self.apply(job_from_dict(job_dict))
            except Exception as e:  # noqa: BLE001 — a malformed marker
                # (arbitrary user JSON) must never kill the daemon loop.
                self.events.warning(
                    key, "TPUJobApplyRejected", f"apply rejected: {e}"
                )

    def scale(self, key: str, worker_replicas: int) -> TPUJob:
        """Elastic resize: change the Worker count and re-rendezvous the gang.

        Requires an elastic_policy; the new count must lie within
        [min_replicas, max_replicas] (reference: torchelastic min/max).
        """
        with self.reconciler.key_lock(key):
            job = self.store.get(key)
            if job is None:
                raise KeyError(key)
            ep = job.spec.elastic_policy
            if ep is None:
                raise ValidationError(["scale: job has no elastic_policy"])
            if not (ep.min_replicas <= worker_replicas <= ep.max_replicas):
                raise ValidationError(
                    [
                        f"scale: worker_replicas={worker_replicas} outside "
                        f"[{ep.min_replicas}, {ep.max_replicas}]"
                    ]
                )
            workers = job.spec.replica_specs.get(ReplicaType.WORKER)
            if workers is None:
                raise ValidationError(["scale: job has no Worker replicas"])
            # Manual resize re-pins the elastic grow-back target: the
            # operator's explicit choice must not be undone by the
            # reconciler growing back to the original submit-time count.
            job.metadata.annotations[ELASTIC_TARGET_ANNOTATION] = str(worker_replicas)
            job.touch()
            if workers.replicas == worker_replicas:
                self.store.update(job)
                return job
            workers.replicas = worker_replicas
            # Membership change → tear down the world; next sync re-creates
            # it with the new WORLD_SIZE (elastic re-rendezvous).
            handles = self.runner.list_for_job(key)
            if handles and not job.is_finished():
                msg = (
                    f"elastic resize to {worker_replicas} workers "
                    f"(restart #{job.status.restart_count + 1})."
                )
                self.reconciler.restart_world(
                    job, key, handles, "TPUJobScaled", msg, warning=False
                )
            self.store.update(job)
            return job

    # ---- reconcile loop ----

    def sync_once(self, now: Optional[float] = None) -> bool:
        """One pass over all jobs; returns True if any job still active.

        The pass is split in two phases. The SERIAL phase syncs — in
        priority order (higher ``scheduling_policy.priority`` first, FIFO
        by submit time within a class, the volcano priorityClass analog) —
        every job whose sync may claim capacity or touch the pass-scoped
        scheduling state (missing replicas, pending restarts/completions,
        elastic jobs, suspend transitions), so under capacity pressure
        high-priority gangs still claim free slots before lower ones. The
        PARALLEL phase fans the remaining steady-state jobs (world
        complete and live — the overwhelming majority at fleet scale)
        across a bounded thread pool; the per-key reconcile locks keep
        each job serialized with CLI-driven mutations. Process liveness is
        polled ONCE for the whole pass (runner.sync), not once per job.
        """
        from .. import obs

        now = time.time() if now is None else now
        t_pass = time.perf_counter()
        if self.shards is not None:
            self._shard_tick(now)
        self._inject_pass_faults()
        any_active = False
        if self.shards is None:
            jobs = self.store.items()
        else:
            # Inline ownership filter: one dict get + one set test per
            # key (10k keys per pass at fleet scale — function-call
            # overhead per key is real money). Validity is computed
            # once; leases are renewed by the background thread, not
            # per key.
            valid = {
                i
                for i in self.shards.owned
                if self.shards.leases[i].held(now)
            }
            cache = self._shard_cache
            jobs = []
            for key, job in self.store.items():
                s = cache.get(key)
                if s is None:
                    s = self._job_shard(key)
                if s in valid:
                    jobs.append((key, job))
        # One batched liveness poll for the whole pass, BEFORE the phase
        # split (the partition reads the freshly observed phases); its
        # change report (None = runner doesn't track) gates the steady
        # fast path below.
        self.runner.sync()
        changed = self.runner.take_changed_keys()
        # Reset the pass-scoped scheduling state (priority reservations,
        # queue-usage cache) before admitting in priority order; close the
        # pass afterwards so solo syncs never see its stale state.
        self.reconciler.begin_pass()
        t_serial = t_steady = 0.0
        fast_skips = 0
        steady: List[str] = []
        self._pass_polled = {}
        self._pass_fast_skipped = set()
        self._pass_no += 1
        try:
            serial: List[tuple] = []
            for key, job in jobs:
                # The merged steady gate, FIRST: a job whose generation
                # still matches both fast-path records was steady AND
                # unfinished at its last full reconcile; with no runner
                # change and the touch()-exempt fields (suspend,
                # elastic_policy) re-checked live, nothing the sync —
                # or even is_finished — reads can have moved. One
                # condition-list walk per job per pass is real money at
                # 10k jobs.
                gen = job.generation
                if (
                    changed is not None
                    and key not in changed
                    and self._steady_gen.get(key) == gen
                    and self._steady_ok.get(key) == gen
                    and not job.spec.run_policy.suspend
                    and job.spec.elastic_policy is None
                    # Serving jobs route requests from the gauge fold
                    # every pass; the fast path's stash-skip would
                    # starve the router between heartbeats.
                    and job.spec.serving is None
                    and self._fast_skip(key, job)
                ):
                    fast_skips += 1
                    self._pass_fast_skipped.add(key)
                    any_active = True
                    continue
                if job.is_finished():
                    self._gc_ttl(job, key, now)
                    continue
                needs = self._needs_scheduling(key, job)
                if not needs:
                    self._steady_ok[key] = gen
                else:
                    self._steady_ok.pop(key, None)
                if not self.parallel_sync or needs:
                    serial.append((key, job))
                    continue
                steady.append(key)
            # Priority order matters only where capacity can be claimed
            # — the serial scheduling phase. Sorting the WHOLE fleet
            # per pass would be O(N log N) of pure overhead at 10k jobs.
            serial.sort(
                key=lambda kj: (
                    -kj[1].spec.run_policy.scheduling_policy.priority,
                    kj[1].status.submit_time or 0.0,
                )
            )
            t0 = time.perf_counter()
            with obs.span("pass_serial", cat="supervisor", jobs=len(serial)):
                for key, job in serial:
                    if self._sync_guarded(key, now):
                        any_active = True
            t_serial = time.perf_counter() - t0
            if steady:
                t0 = time.perf_counter()
                with obs.span(
                    "pass_steady", cat="supervisor", jobs=len(steady)
                ):
                    for active in self._sync_parallel(steady, now):
                        any_active = any_active or active
                t_steady = time.perf_counter() - t0
                # Arm the fast path: these jobs just had a full
                # reconcile with nothing to schedule; record the
                # generation that reconcile left behind.
                for key in steady:
                    job = self.store.get(key)
                    if job is not None and not job.is_finished():
                        self._steady_gen[key] = job.generation
            if self.preempt_enabled:
                self._maybe_preempt(jobs, now)
        finally:
            queue_usage = self.reconciler.end_pass()
            obs.flush()  # this pass's spans, for a live `tpujob trace`
        if fast_skips:
            self.metrics.steady_fast_skips.inc(fast_skips)
        self._update_gauges(jobs, queue_usage)
        m = self.metrics.sync_pass_seconds
        m.observe(t_serial, phase="serial")
        if t_steady:
            m.observe(t_steady, phase="steady")
        t_total = time.perf_counter() - t_pass
        m.observe(t_total, phase="total")
        self.metrics.supervisor_pass_seconds.set(
            t_total, supervisor=self.identity
        )
        # Latency-driven pool autoscaling: feed the measured steady
        # phase; resize takes effect next pass.
        self._resize_pool(self._pool_scaler.observe(t_steady, len(steady)))
        return any_active

    def _sync_guarded(self, key: str, now: float) -> bool:
        """Reconcile with the shard double-reconcile guard: a lease that
        stopped being valid since the pass started (renewal fencing-
        rejected, expiry mid-pass) refuses the sync — the new owner
        reconciles the job; we must not race it."""
        if self.shards is not None and not self._owns_key(key):
            self.shards.io.guard_skips += 1
            self.metrics.shard_guard_skips.inc()
            return True  # still active; its new owner reconciles it
        return self.reconciler.sync(key, now=now)

    def _fast_skip(self, key: str, job: TPUJob) -> bool:
        """The tail of the merged steady gate (the caller already
        verified: runner unchanged, generation matches both fast-path
        records, suspend/elastic clear): refuse when a time-driven rule
        (active deadline, hang deadline) is armed, then check the one
        remaining input — did the job's status files grow?"""
        if job.spec.run_policy.active_deadline_seconds is not None:
            return False
        if HANG_DEADLINE_ANNOTATION in job.metadata.annotations:
            return False
        stagger = self._dir_empty.get(key)
        if stagger is not None and (self._pass_no & 3) != stagger:
            # The dir held no replica files at the last real scan: a
            # never-reported job's first file appears at most 3 passes
            # late on the telemetry surfaces (nothing else reads it),
            # and 10k such jobs cost ~2.5k scandirs per pass, not 10k.
            self._pass_polled[key] = {}
            return True
        tailer = self._progress
        by_kind = tailer.poll(job_status_dir(self.reconciler.status_root, key))
        self._pass_polled[key] = by_kind
        if tailer.last_poll_consumed:
            self._dir_empty.pop(key, None)
            return False
        if tailer.last_poll_files == 0:
            if stagger is None:
                self._dir_empty[key] = zlib.crc32(key.encode()) & 3
        else:
            self._dir_empty.pop(key, None)
        return True

    def _needs_scheduling(self, key: str, job: TPUJob) -> bool:
        """Must this job sync in the serial scheduling phase? True when
        its sync may create replicas, claim capacity, or read/write the
        pass-scoped reservation state — anything whose correctness
        depends on priority ordering within the pass."""
        if job.spec.elastic_policy is not None:
            return True  # grow-back reads reservations/queue budgets
        if job.get_condition(ConditionType.CREATED) is None:
            return True  # first sync: creation + status-dir reset
        if job.spec.run_policy.suspend or job.has_condition(
            ConditionType.SUSPENDED
        ):
            return True  # teardown / resume-relaunch transitions
        if not self.expectations.satisfied(key):
            return True
        handles = {h.name: h for h in self.runner.list_for_job(key)}
        for rtype, rs in job.spec.replica_specs.items():
            for index in range(rs.replicas or 0):
                h = handles.get(replica_name(key, rtype, index))
                if h is None or h.is_finished():
                    # Missing replica (admission) or a finished one
                    # (restart classification / job completion).
                    return True
        return False

    def _resize_pool(self, size: int) -> None:
        """Apply an autoscaler decision. The pool is idle between passes
        (observe() runs after the steady phase drained), so a resize is
        a cheap shutdown + lazy re-create; same-size calls are free."""
        self._sync_workers = size
        self.metrics.sync_pool_size.set(size)
        self.metrics.sync_pool_max.set(self._pool_scaler.ceiling)
        with self._sync_pool_lock:
            if self._sync_pool is not None and self._sync_pool_size != size:
                pool, self._sync_pool = self._sync_pool, None
            else:
                return
        pool.shutdown(wait=True)

    def _sync_parallel(self, keys: List[str], now: float) -> List[bool]:
        """Fan steady-state reconciles across the bounded pool, in chunks
        so pool overhead stays O(workers), not O(jobs). Exceptions
        propagate like the serial loop's (first one wins)."""
        if len(keys) <= 1 or self._sync_workers <= 1:
            return [self._sync_guarded(k, now) for k in keys]
        with self._sync_pool_lock:
            if self._sync_pool is None:
                self._sync_pool = ThreadPoolExecutor(
                    max_workers=self._sync_workers,
                    thread_name_prefix="tpujob-sync",
                )
                self._sync_pool_size = self._sync_workers
            pool = self._sync_pool

        def run_chunk(chunk: List[str]) -> List[bool]:
            return [self._sync_guarded(k, now) for k in chunk]

        n_chunks = min(len(keys), 2 * self._sync_workers)
        step = (len(keys) + n_chunks - 1) // n_chunks
        futures = [
            pool.submit(run_chunk, keys[i : i + step])
            for i in range(0, len(keys), step)
        ]
        out: List[bool] = []
        for f in futures:
            out.extend(f.result())
        return out

    def _inject_pass_faults(self) -> None:
        """The per-pass fault-injection hook: when a plan is armed
        (``tpujob chaos`` / tests), ``kill_replica`` faults scheduled
        for this pass SIGKILL their targets through the runner — the
        deterministic stand-in for host preemption. A single ``is
        None`` check when nothing is armed."""
        from .. import faults

        inj = faults.active()
        if inj is None:
            return
        self._fault_pass += 1
        if inj.supervisor_kill_due(self._fault_pass, self.identity):
            self.events.warning(
                SHARD_EVENT_KEY,
                "FaultInjected",
                f"injected supervisor kill of {self.identity} "
                f"(pass {self._fault_pass}).",
            )
            if self.fault_kill_action is not None:
                self.fault_kill_action()
            else:
                os._exit(137)  # a real daemon dies without cleanup
        if self.shards is not None:
            for f in inj.lease_drops_due(
                self._fault_pass, self.shards.owned
            ):
                dropped = self.shards.inject_drop(f.target)
                self.events.warning(
                    SHARD_EVENT_KEY,
                    "FaultInjected",
                    f"injected on-disk lease drop of shard(s) {dropped} "
                    f"held by {self.identity} ({f.label()}).",
                )
        for f in inj.kills_due(self._fault_pass):
            for h in self.runner.list_all():
                if h.is_active() and faults.FaultInjector.target_matches(
                    f.target, h.replica_type.value, h.index
                ):
                    self.runner.inject_kill(h.name)
                    self.events.warning(
                        h.job_key,
                        "FaultInjected",
                        f"injected kill of {h.name} ({f.label()}).",
                    )
        for f in inj.preempts_due(self._fault_pass):
            for h in self.runner.list_all():
                if h.is_active() and faults.FaultInjector.target_matches(
                    f.target, h.replica_type.value, h.index
                ):
                    self.runner.inject_preempt(h.name)
                    self.events.warning(
                        h.job_key,
                        "FaultInjected",
                        f"injected preemption of {h.name} ({f.label()}).",
                    )
        for f in inj.storms_due(self._fault_pass):
            victims = [
                h
                for h in self.runner.list_all()
                if h.is_active()
                and faults.FaultInjector.target_matches(
                    f.target, h.replica_type.value, h.index
                )
            ][: max(1, f.times)]
            for h in victims:
                self.runner.inject_kill(h.name)
                self.events.warning(
                    h.job_key,
                    "FaultInjected",
                    f"injected kill of {h.name} "
                    f"({f.label()}, storm of {len(victims)} this pass).",
                )
        if any(f.kind == "overload_spool" for f in inj.plan.faults):
            # Offered-rate burst: drop ``times`` synthetic requests into
            # each targeted serving job's ingress spool — the
            # deterministic stand-in for a client flood (queue growth /
            # SLO burn the remediation engine must autoscale against).
            from ..serving.router import front_spool_dir
            from ..serving.spool import Spool, make_request

            for key, job in self.store.items():
                if job.spec.serving is None:
                    continue
                for f in inj.overloads_due(self._fault_pass, key):
                    sp = Spool(
                        front_spool_dir(
                            self.router.serve_root, key, job.spec.serving
                        )
                    )
                    sp.enqueue_batch(
                        [
                            make_request(prompt_len=16, max_new_tokens=8)
                            for _ in range(max(1, f.times))
                        ],
                        fsync=False,
                    )
                    self.events.warning(
                        key,
                        "FaultInjected",
                        f"injected {max(1, f.times)} overload request(s) "
                        f"into the front spool ({f.label()}).",
                    )

    def _update_gauges(self, jobs, queue_usage: Optional[dict]) -> None:
        """Point-in-time scheduler state for /metrics, refreshed per pass
        from the pass's own accounting (no rescans)."""
        m = self.metrics
        # Fast-skipped jobs are unfinished by construction (the pass
        # loop checked); walking every job's conditions again tripled
        # the is_finished cost per pass at 10k jobs.
        skipped = self._pass_fast_skipped
        m.jobs_active.set(
            len(skipped)
            + sum(
                1
                for key, j in jobs
                if key not in skipped and not j.is_finished()
            )
        )
        n_active = 0
        slots_used = 0
        for h in self.runner.list_all():
            if h.is_active():
                n_active += 1
                slots_used += h.slots
        m.replicas_active.set(n_active)
        m.slots_used.set(slots_used)
        m.slots_capacity.set(self.runner.capacity_slots() or 0)
        m.gangs_held.set(len(self.reconciler.held_gangs()))
        m.queue_slots_used.clear()
        m.queue_slots_capacity.clear()
        if self.reconciler.queue_slots and queue_usage is not None:
            for qname, cap in self.reconciler.queue_slots.items():
                m.queue_slots_capacity.set(cap, queue=qname)
                m.queue_slots_used.set(queue_usage.get(qname, 0), queue=qname)
        # Elastic world state: current world size per unfinished elastic
        # job (tagged with the pre-shrink target so `3→4` is readable off
        # /metrics alone) and the warm hot-spare pool depth; the same walk
        # folds hot_spares demand into the standby pool target.
        m.world_size.clear()
        hot_want = self._standby_base
        for key, j in jobs:
            ep = j.spec.elastic_policy
            if ep is None:
                continue
            if key not in skipped and j.is_finished():
                continue
            hot_want = max(hot_want, ep.hot_spares)
            target = j.metadata.annotations.get(ELASTIC_TARGET_ANNOTATION)
            m.world_size.set(
                j.spec.total_replicas(),
                job=key,
                target=str(target) if target else "",
            )
        m.hot_spares.set(self.runner.standby_ready())
        if hot_want != self._standby_want:
            self.runner.set_standby_target(hot_want)
            self._standby_want = hot_want
        if self.shards is not None:
            m.shards_owned.set(len(self.shards.owned))
            m.shard_jobs.clear()
            per_shard: dict = {}
            cache = self._shard_cache
            for key, j in jobs:
                if key in skipped or not j.is_finished():
                    s = cache.get(key)
                    if s is None:
                        s = self._job_shard(key)
                    per_shard[s] = per_shard.get(s, 0) + 1
            for s, n in per_shard.items():
                m.shard_jobs.set(
                    n, shard=str(s), supervisor=self.identity
                )
        self._update_progress_gauges(jobs)
        # End-of-pass cross-job rule (noisy-neighbor attribution needs
        # every job's verdict from THIS pass), then the alert gauges.
        self.watch.correlate()
        self.watch.export_gauge(m.alerts_firing)
        self._fold_io_counters()

    def _fold_io_counters(self) -> None:
        """Mirror the bench-only I/O instrumentation (StoreIOCounters,
        ProgressTailer fold stats) onto live registry counters, once per
        pass, as deltas — an idle-I/O regression shows on /metrics in
        production, not just in the control-plane bench."""
        m = self.metrics
        cur = self.store.io.snapshot()
        for k, counter in m.store_io.items():
            delta = cur[k] - self._store_io_seen.get(k, 0)
            if delta:
                counter.inc(delta)
        self._store_io_seen = cur
        cur = self._progress.io.snapshot()
        for k, counter in m.progress_io.items():
            delta = cur[k] - self._progress_io_seen.get(k, 0)
            if delta:
                counter.inc(delta)
        self._progress_io_seen = cur
        cur = self.router.io_snapshot()
        for k, counter in m.router_io.items():
            delta = cur[k] - self._router_io_seen.get(k, 0)
            if delta:
                counter.inc(delta)
        self._router_io_seen = cur
        # Per-lane deltas (tpujob_router_*_total{lane}): the snapshot
        # is monotonic across job retire by construction, so a plain
        # delta fold is safe here too.
        lane_cur = self.router.lane_io_snapshot()
        for idx, vals in lane_cur.items():
            seen = self._router_lane_seen.get(idx, {})
            for k, counter in m.router_lane_io.items():
                delta = vals.get(k, 0) - seen.get(k, 0)
                if delta:
                    counter.inc(delta, lane=str(idx))
        self._router_lane_seen = lane_cur

    def _update_progress_gauges(self, jobs) -> None:
        """Fold each unfinished job's newest workload heartbeat
        (controller/progress.py) into the per-job training gauges — the
        SURVEY §5 "steps/sec + images/sec/chip meters" on /metrics.
        Cleared-and-rebuilt per pass so finished/deleted jobs don't
        linger as stale series; the incremental tailer reads only bytes
        appended since the last pass (an idle job costs zero reads)."""
        m = self.metrics
        g_step, g_sps, g_tp, g_loss, g_age = (
            m.job_step, m.job_steps_per_sec, m.job_throughput, m.job_loss,
            m.job_progress_age,
        )
        gauges = (
            g_step, g_sps, g_tp, g_loss, g_age,
            m.job_checkpoint_step, m.job_ckpt_queue_depth,
            m.job_ckpt_oldest_age, m.job_ckpt_stage_depth,
            m.job_feed_stall,
        )
        for g in gauges:
            g.clear()
        from .progress import job_status_dir

        root = self.reconciler.status_root
        if root is None:
            return
        skipped = self._pass_fast_skipped
        polled = self._pass_polled
        for key, job in jobs:
            if key in skipped and not polled.get(key, True):
                # Fast-skipped with an EMPTY poll stash: the job has
                # never produced a status record (the tailer state is
                # empty, not just quiet), so there is nothing to fold,
                # observe, or probe — skip the whole body. At 10k
                # never-reporting jobs this loop is otherwise the
                # biggest residual per-pass cost.
                continue
            if key not in skipped and job.is_finished():
                # Close the live-alert lifecycle: anything still firing
                # resolves (logged) so the postmortem sees it closed by
                # the finish, not dangling. Idempotent after the first
                # pass (state already dropped).
                self.watch.finalize(key)
                self.remediation.finalize(key)
                if (
                    job.spec.serving is not None
                    and key not in self._serve_finalized
                ):
                    # Serve-plane end-of-life: drain the front queue
                    # with terminal error responses so no client waits
                    # out a timeout. Once — the guard set keeps a
                    # finished-but-undeleted serving job from paying a
                    # spool scan every pass.
                    self._serve_finalized.add(key)
                    self.router.finalize(key, job)
                    self.router.retire_job(key)
                continue
            status_dir = job_status_dir(root, key)
            if key in self._pass_polled:
                # The fast-path gate already polled this dir this pass;
                # poll() returns latest-known state, so the stash is
                # exactly what a second (wasted) scan would return.
                by_kind = self._pass_polled[key]
            else:
                by_kind = self._progress.poll(status_dir)
            by_replica = self._progress.replica_latest(status_dir)
            self._record_clock_observations(key, status_dir, by_replica)
            # Live health engine: fold the same already-tailed state
            # (zero I/O) and run the shared detector rules. Jobs that
            # never reported stay untracked — evaluation is skipped
            # entirely, so an idle fleet pays one dict lookup per job
            # here. No event list is passed: live silence is judged
            # against the supervisor clock (a recorded kill is the
            # OFFLINE engine's evidence; live it would pin a stale
            # alert across the restart that healed it).
            self.watch.observe(key, by_replica)
            if self.watch.tracked(key):
                self.watch.evaluate(key, job=job)
            rec = by_kind.get("progress")
            if rec is not None:
                if rec.get("step") is not None:
                    g_step.set(float(rec["step"]), job=key)
                if rec.get("steps_per_sec") is not None:
                    g_sps.set(float(rec["steps_per_sec"]), job=key)
                if rec.get("throughput") is not None:
                    g_tp.set(
                        float(rec["throughput"]),
                        job=key,
                        unit=str(rec.get("unit") or "units/sec"),
                    )
                if rec.get("loss") is not None:
                    g_loss.set(float(rec["loss"]), job=key)
                if rec.get("feed_stall_ms") is not None:
                    m.job_feed_stall.set(float(rec["feed_stall_ms"]), job=key)
                # Staleness signal: without it a hung job's meter reads
                # as a healthy rate forever.
                g_age.set(max(time.time() - rec["ts"], 0.0), job=key)
                # Step-time distribution, one observation per NEW
                # heartbeat (interval-averaged: each heartbeat's rate is
                # already a mean over its reporting window).
                sps = rec.get("steps_per_sec")
                if sps and rec["ts"] > self._hb_observed.get(key, 0.0):
                    self._hb_observed[key] = rec["ts"]
                    st = rec.get("step_time_ms")
                    # Exemplar = the span coordinates of the step this
                    # beat reported: `tpujob top`/`why` can jump from a
                    # histogram cell straight to the trace span.
                    ex = (
                        f"{rec.get('replica', '?')}/step:{int(rec['step'])}"
                        if rec.get("step") is not None
                        else None
                    )
                    m.step_time_seconds.observe(
                        st / 1000.0 if st is not None else 1.0 / float(sps),
                        exemplar=ex,
                        job=key,
                    )
            ck = by_kind.get("checkpoint_committed")
            if ck is not None:
                if ck.get("step") is not None:
                    m.job_checkpoint_step.set(float(ck["step"]), job=key)
                if ck.get("queue_depth") is not None:
                    m.job_ckpt_queue_depth.set(
                        float(ck["queue_depth"]), job=key
                    )
                if ck.get("oldest_age_s") is not None:
                    m.job_ckpt_oldest_age.set(
                        float(ck["oldest_age_s"]), job=key
                    )
                if ck.get("stage_depth") is not None:
                    m.job_ckpt_stage_depth.set(
                        float(ck["stage_depth"]), job=key
                    )
                if (
                    ck.get("commit_ms") is not None
                    and ck["ts"] > self._ckpt_observed.get(key, 0.0)
                ):
                    self._ckpt_observed[key] = ck["ts"]
                    ex = (
                        f"{ck.get('replica', '?')}/ckpt_commit:{int(ck['step'])}"
                        if ck.get("step") is not None
                        else None
                    )
                    m.checkpoint_commit_seconds.observe(
                        float(ck["commit_ms"]) / 1000.0, exemplar=ex, job=key
                    )
            serve_summary = None
            if job.spec.serving is not None:
                # Serve plane: route this job's requests on the pass
                # cadence. The replica set is the runner's handle index
                # (the same truth reconcile acts on); per-replica load
                # comes from the serve telemetry already tailed above —
                # the router adds no fold I/O of its own.
                serve_summary = self.router.tick(
                    key,
                    job,
                    self.runner.list_for_job(key),
                    by_replica,
                    status_dir=status_dir,
                )
            if job.spec.remediation is not None:
                # Close the loop (controller/remediation.py): this
                # pass's firing alerts — which include noisy_neighbor
                # from the PREVIOUS pass's correlate(), the freshest
                # verdict that exists when this job is folded — plus
                # the router summary drive at most one fenced action.
                firing = (
                    self.watch.active_alerts(key)
                    if self.watch.tracked(key)
                    else []
                )
                self.remediation.evaluate(
                    key, job, firing, serve=serve_summary
                )

    def _record_clock_observations(
        self, key: str, status_dir, by_replica: Optional[dict] = None
    ) -> None:
        """Pair each replica's NEW heartbeat-send timestamp with this
        supervisor's observe time and append it to the job's clock log —
        the raw material for the cross-host offset estimator
        (obs/clock.py). Zero I/O when no replica beat since the last
        pass; first sight of a replica primes the dedup without logging
        (see __init__).

        Round-trip probes ride the same fold: a job with fresh beats
        gets a probe file rewrite at most every PROBE_INTERVAL_S
        (supervisor write ts + seq); replicas echo it as a
        ``clock_probe`` status record whose (probe write, echo send,
        echo observe) triple kills the one-way delay bias in the
        estimator. Idle jobs never probe — the zero-idle-I/O invariant
        holds."""
        if by_replica is None:
            by_replica = self._progress.replica_latest(status_dir)
        if not by_replica:
            return
        from ..obs.clock import PROBE_INTERVAL_S, write_probe

        now = time.time()
        new_beat = False
        for replica, kinds in by_replica.items():
            rec = kinds.get("progress")
            if rec is not None:
                seen = self._clock_seen.get((key, replica))
                if seen is not None and rec["ts"] > seen:
                    self._clock_log(key).observe(replica, rec["ts"], now)
                if seen is None or rec["ts"] > seen:
                    self._clock_seen[(key, replica)] = rec["ts"]
                    new_beat = True
            echo = kinds.get("clock_probe")
            if echo is not None and echo.get("probe_ts") is not None:
                seen = self._probe_seen.get((key, replica))
                if (seen is None or echo["ts"] > seen) and int(
                    echo.get("seq", -1)
                ) in self._probe_seqs.get(key, ()):
                    # An echo of a probe THIS process wrote (stale
                    # echoes from before a daemon restart are rejected
                    # by seq, so no first-sight priming is needed).
                    self._probe_seen[(key, replica)] = echo["ts"]
                    self._clock_log(key).observe(
                        replica, echo["ts"], now,
                        probe_ts=float(echo["probe_ts"]),
                    )
        if new_beat and now - self._probe_written.get(key, 0.0) >= PROBE_INTERVAL_S:
            self._probe_written[key] = now
            seq = write_probe(status_dir, now)
            if seq is not None:
                # Keep the last few: a replica may echo the previous
                # probe in the same window a rewrite lands.
                self._probe_seqs.setdefault(key, []).append(seq)
                del self._probe_seqs[key][:-4]

    def _clock_log(self, key: str):
        log = self._clock_logs.get(key)
        if log is None:
            from ..obs.clock import ClockLog, job_clock_log

            log = ClockLog(job_clock_log(self.state_dir, key))
            self._clock_logs[key] = log
        return log

    def _maybe_preempt(self, jobs, now: float) -> None:
        """volcano ``preempt``: evict lower-priority running worlds so the
        highest-priority held gang can fit next pass.

        Victims are chosen strictly below the preemptor's priority, lowest
        priority first and newest submission first within a class, whole
        worlds at a time, and only if evicting them actually covers the
        shortfall (no pointless evictions). Victims relaunch later behind
        the preemptor's reservation; their restart budget is untouched.
        """
        held = self.reconciler.held_gangs()
        if not held:
            return
        slots = self.runner.schedulable_slots()
        if slots is None:
            return  # unbounded capacity: holds are not capacity-driven
        by_key = dict(jobs)
        # The single highest-priority held gang preempts (FIFO tie-break).
        key = min(
            held,
            key=lambda k: (
                -held[k][1],
                (by_key[k].status.submit_time or 0.0) if k in by_key else 0.0,
            ),
        )
        need, prio = held[key]
        shortfall = need - slots
        if shortfall <= 0:
            return
        victims = []
        freed = 0
        candidates = [
            (k, j)
            for k, j in jobs
            if k != key
            and not j.is_finished()
            and j.spec.run_policy.scheduling_policy.priority < prio
        ]
        # Lowest priority first; newest first within a class.
        candidates.sort(
            key=lambda kj: (
                kj[1].spec.run_policy.scheduling_policy.priority,
                -(kj[1].status.submit_time or 0.0),
            )
        )
        for vkey, vjob in candidates:
            active = [h for h in self.runner.list_for_job(vkey) if h.is_active()]
            if not active:
                continue
            victims.append((vkey, vjob, active))
            freed += sum(h.slots for h in active)  # device-slot weights
            if freed >= shortfall:
                break
        if freed < shortfall:
            return  # even evicting every lower class would not fit the gang
        for vkey, _, active in victims:
            with self.reconciler.key_lock(vkey):
                # Re-fetch under the lock: a concurrent delete_job must not
                # be resurrected by store.update on a stale snapshot.
                vjob = self.store.get(vkey)
                if vjob is None or vjob.is_finished():
                    continue
                self.reconciler.preempt_world(vjob, vkey, active, key, now=now)
                self.store.update(vjob)

    def _retire_job_telemetry(self, key: str) -> None:
        """Metric lifecycle on job deletion (reconciler GC, TTL, CLI
        delete): drop the job's per-job histogram/gauge series from the
        live registry and forget the supervisor-side fold state — the
        ROADMAP unbounded-cardinality fix. A churn of N jobs leaves the
        registry bounded (pinned by tests/test_obs_analyze.py)."""
        self.metrics.retire_job(key)
        self.watch.retire_job(key)
        self.remediation.retire_job(key)
        self.router.retire_job(key)
        self._serve_finalized.discard(key)
        self._steady_gen.pop(key, None)
        self._steady_ok.pop(key, None)
        self._dir_empty.pop(key, None)
        self._shard_cache.pop(key, None)
        self._hb_observed.pop(key, None)
        self._ckpt_observed.pop(key, None)
        self._clock_logs.pop(key, None)
        self._probe_written.pop(key, None)
        self._probe_seqs.pop(key, None)
        for k in [k for k in self._clock_seen if k[0] == key]:
            del self._clock_seen[k]
        for k in [k for k in self._probe_seen if k[0] == key]:
            del self._probe_seen[k]

    def _gc_ttl(self, job: TPUJob, key: str, now: float) -> None:
        """TTLSecondsAfterFinished → delete the job object (SURVEY.md §3.4)."""
        ttl = job.spec.run_policy.ttl_seconds_after_finished
        if ttl is None or job.status.completion_time is None:
            return
        if now - job.status.completion_time >= ttl:
            self.delete_job(key)

    def wait(self, key: str, timeout: Optional[float] = None) -> TPUJob:
        """Reconcile THIS job until it finishes (or timeout); returns it.

        Only the named job is synced — a foreground ``tpujob run`` must not
        also reconcile jobs owned by a daemon sharing the state dir (two
        supervisors spawning duplicate worlds for the same job).
        """
        # monotonic: an NTP step while a caller waits must not stretch
        # (job hangs past its timeout) or collapse (spurious TimeoutError
        # on a healthy job) the budget.
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            self.reconciler.sync(key)
            job = self.store.get(key)
            if job is None:
                raise KeyError(f"job {key} disappeared (TTL GC or deletion)")
            if job.is_finished():
                return job
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError(f"job {key} did not finish within {timeout}s")
            time.sleep(self.poll_interval)

    def run(self, job: TPUJob, timeout: Optional[float] = None) -> TPUJob:
        """Submit and reconcile to completion (foreground ``tpujob run``)."""
        key = self.submit(job)
        return self.wait(key, timeout=timeout)

    def process_deletion_markers(self) -> None:
        """Act on cross-process ``tpujob delete`` requests: this process owns
        the replica processes, so it performs the kill + record removal."""
        for key in self.store.deletion_markers():
            with self.reconciler.key_lock(key):
                # Read the purge request BEFORE acting; purge happens after
                # the replicas are dead, so a running workload can't
                # re-create the checkpoint dir behind the purge.
                purge = self.store.marker_requests_purge(key)
                uid = self.store.marker_uid(key)
                cur = self.store.get(key)
                if cur is not None and uid and cur.metadata.uid != uid:
                    # The marker targets a PREVIOUS incarnation. Never
                    # kill the new job — but the old incarnation's
                    # replica records may still exist (`tpujob submit`
                    # writes the store record directly, with no runner to
                    # reap through): leaving them would let the
                    # reconciler adopt a stale SUCCEEDED exit record and
                    # complete the new job without running it. Replicas
                    # created before the new incarnation was accepted are
                    # provably the old job's.
                    born = cur.metadata.creation_timestamp or 0.0
                    # created_at == 0.0 means the record predates the
                    # field (unknown age). Unknown-age ACTIVE replicas
                    # are spared — this branch must never be able to
                    # kill the new incarnation's running world — but
                    # unknown-age FINISHED records are reaped: leaving a
                    # stale SUCCEEDED exit record would let the
                    # reconciler adopt it and complete the new job
                    # without running it, and reaping a finished record
                    # can at worst trigger a re-create, never kill live
                    # work.
                    stale = [
                        h.name
                        for h in self.runner.list_for_job(key)
                        if (h.created_at and h.created_at < born)
                        or (not h.created_at and h.is_finished())
                    ]
                    if stale:
                        self.runner.delete_many(stale)
                    self.store.clear_deletion_marker(key)
                    continue
                self.delete_job(key, purge_artifacts=purge)
                self.store.clear_deletion_marker(key)

    def process_suspend_markers(self) -> None:
        """Act on cross-process ``tpujob suspend``/``resume`` requests."""
        for key, flag in self.store.take_suspend_markers():
            with self.reconciler.key_lock(key):
                job = self.store.get(key)
                if job is None or job.is_finished():
                    continue
                if job.spec.run_policy.suspend != flag:
                    job.spec.run_policy.suspend = flag
                    job.touch()
                    self.store.update(job)

    def process_scale_markers(self) -> None:
        """Act on cross-process ``tpujob scale`` requests (elastic resize)."""
        for key, workers in self.store.take_scale_markers():
            try:
                self.scale(key, workers)
            except (KeyError, ValidationError) as e:
                self.events.warning(
                    key, "TPUJobScaleRejected", f"scale to {workers} rejected: {e}"
                )

    def metrics_file_path(self) -> Path:
        """Unsharded daemons keep the historical ``metrics.prom``; a
        sharded supervisor writes ``metrics-<identity>.prom`` so N
        daemons on one state dir don't clobber each other — observer
        surfaces (`tpujob top`, `metrics`, `why`) read the union."""
        if self.shards is None:
            return self.state_dir / "metrics.prom"
        import re as _re

        safe = _re.sub(r"[^A-Za-z0-9._-]", "_", self.identity)
        return self.state_dir / f"metrics-{safe}.prom"

    def write_metrics_file(self) -> None:
        """Expose counters for ``tpujob metrics`` (monitoring-port analog).

        tmp+replace: ``tpujob top`` polls this file on a timer and must
        never read a half-rendered exposition page.
        """
        path = self.metrics_file_path()
        tmp = path.with_suffix(path.suffix + ".tmp")
        tmp.write_text(self.metrics.render_text())
        tmp.replace(path)

    def shutdown(self) -> None:
        with self._sync_pool_lock:
            pool, self._sync_pool = self._sync_pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        if isinstance(self.runner, SubprocessRunner):
            self.runner.shutdown()
        self.router.close()
        if self.shards is not None:
            # Voluntary drain: hand every shard back NOW so survivors
            # rebalance immediately instead of waiting out the TTL.
            self.shards.drain()
        if self.lease is not None:
            self.lease.release()


def schedule_to_first_step_latency(job: TPUJob) -> Optional[float]:
    """The north-star latency metric (BASELINE.json:2): submit-accepted →
    first training step executed."""
    if job.status.submit_time is None or job.status.first_step_time is None:
        return None
    return job.status.first_step_time - job.status.submit_time


def job_timeline(job: TPUJob):
    """Lifecycle spans for ``tpujob describe`` (SURVEY.md §5 tracing:
    supervisor timing spans). Derived from status timestamps, so it costs
    nothing to record: submit → gang launch → first step → finish."""
    s = job.status
    spans = []

    def span(name, t0, t1):
        if t0 is not None and t1 is not None and t1 >= t0:
            spans.append((name, t1 - t0))

    span("submit -> replicas launched", s.submit_time, s.start_time)
    span("launch -> first step", s.start_time, s.first_step_time)
    span("first step -> finished", s.first_step_time, s.completion_time)
    span("total (submit -> finished)", s.submit_time, s.completion_time)
    return spans
