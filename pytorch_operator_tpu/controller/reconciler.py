"""The TPUJob reconciler — the operator brain.

Reference: ``PyTorchController.syncPyTorchJob`` / ``JobController.
ReconcileJobs`` (SURVEY.md §3.2): claim replicas, diff desired vs actual,
create missing replicas with injected cluster-spec env, classify failures
under restart policies, drive the condition state machine, clean up on
completion.

One :meth:`sync` call is one reconcile pass — exactly the unit the
reference's unit tests exercise against fake clientsets (SURVEY.md §4); here
the same tests run against :class:`~.runner.FakeRunner`.
"""

from __future__ import annotations

import json
import re
import threading
import time
from pathlib import Path
from typing import List, Optional

from ..api.defaults import (
    AUTO_PORT_ANNOTATION,
    ELASTIC_TARGET_ANNOTATION,
    HANG_DEADLINE_ANNOTATION,
    set_defaults,
)
from ..api.types import (
    CleanPodPolicy,
    ConditionType,
    ReplicaPhase,
    ReplicaType,
    RestartPolicy,
    TPUJob,
)
from ..runtime.env import build_cluster_env
from .elastic import (
    RESIZE,
    build_resize_record,
    classify_death,
    clear_resize_record,
    member_id,
    read_resize_record,
    reassign_ranks,
    write_resize_record,
)
from .events import EventRecorder
from .expectations import ControllerExpectations
from .gang import GangScheduler
from .metrics import MetricsRegistry
from .runner import ProcessRunner, ReplicaHandle, replica_name, replica_slots
from .store import key_to_fs
from .status import (
    ACTION_FAIL_JOB,
    ACTION_NONE,
    ACTION_RESTART,
    classify_exit,
    master_handle,
    update_replica_statuses,
)

# Crash-loop backoff schedule (kubelet CrashLoopBackOff analog): the
# FIRST failure respawns immediately (preemption recovery must not
# wait), then a replica that keeps dying QUICKLY respawns after
# base * 2^(streak-2) seconds, capped; a failed run that lived at least
# the reset uptime counts as healthy-then-died and restarts the streak.
CRASH_BACKOFF_BASE_S = 1.0
CRASH_BACKOFF_CAP_S = 300.0
CRASH_RESET_UPTIME_S = 600.0

# Grow-back holdoff after an in-place resize: growing is a whole-gang
# re-rendezvous (restart-based), so chasing capacity immediately after a
# shrink would convert every partial-gang death into shrink→restart churn
# — exactly the thrash the resize path exists to avoid. The
# world_resize_thrash detector (obs/rules.py) alerts when churn happens
# anyway.
RESIZE_GROW_HOLDOFF_S = 30.0


class Reconciler:
    def __init__(
        self,
        store,
        runner: ProcessRunner,
        events: Optional[EventRecorder] = None,
        metrics: Optional[MetricsRegistry] = None,
        gang: Optional[GangScheduler] = None,
        expectations: Optional[ControllerExpectations] = None,
        status_root: Optional[Path] = None,
        checkpoint_root: Optional[Path] = None,
        coordinator_host: str = "127.0.0.1",
        queue_slots: Optional[dict] = None,
        trace_root: Optional[Path] = None,
        serve_root: Optional[Path] = None,
    ):
        self.store = store
        self.runner = runner
        self.events = events or EventRecorder()
        self.metrics = metrics or MetricsRegistry()
        self.gang = gang or GangScheduler(enabled=True)
        self.expectations = expectations or ControllerExpectations()
        self.status_root = Path(status_root) if status_root else None
        self.checkpoint_root = Path(checkpoint_root) if checkpoint_root else None
        # Per-job span files land under here when a job's spec opts into
        # tracing (spec.observability.trace) or the supervisor itself is
        # traced (TPUJOB_TRACE_DIR armed — trace everything).
        self.trace_root = Path(trace_root) if trace_root else None
        # Serve plane (serving/router.py): serving jobs' spool trees
        # live under here; each serving replica gets a private spool
        # injected as TPUJOB_SPOOL_DIR. None = serve plane off.
        self.serve_root = Path(serve_root) if serve_root else None
        self.coordinator_host = coordinator_host
        # Per-queue replica-slot caps (volcano queue analog): jobs name a
        # queue in scheduling_policy; admission is bounded by the queue's
        # free capacity. None = no queue enforcement.
        self.queue_slots = dict(queue_slots) if queue_slots else None
        # Pass-scoped scheduling state (begin_pass): per-key slots reserved
        # by held gangs (a job never blocks ITSELF — only jobs synced after
        # it in priority order), and a queue-usage cache so a pass is
        # O(jobs) not O(jobs²) in queue accounting.
        self._pass_reservations: dict = {}
        self._pass_queue_used = None
        # Gangs held this pass: {key: (min_needed, priority)} — the input
        # to the supervisor's optional preemption step (volcano `preempt`).
        self._pass_held: dict = {}
        self._in_pass = False
        self._unschedulable_warned = set()
        # Per-file byte offsets for incremental status-report scanning.
        self._scan_offsets = {}
        # Per-key serialization (reference: the workqueue processes each job
        # key on one worker at a time). Two concurrent syncs of one job
        # would both observe a missing replica and double-create it.
        self._key_locks: dict = {}
        self._key_locks_guard = threading.Lock()
        # Crash-loop backoff (kubelet CrashLoopBackOff analog — the
        # reference delegates per-pod respawn damping to the kubelet;
        # this supervisor IS its own kubelet). replica name ->
        # (consecutive quick failures, earliest respawn time). A replica
        # whose failed run lived >= CRASH_RESET_UPTIME_S counts as
        # healthy-then-died and resets the streak, so long-running jobs
        # killed by preemption restart after one base delay while a
        # replica dying at startup backs off exponentially instead of
        # respawning every sync pass (observed: an argparse-rejected
        # workload restarted ~2x/second, 1300 restarts in 10 minutes).
        self._crash_backoff: dict = {}
        # key -> wall time of the last in-place resize; gates elastic
        # grow-back for RESIZE_GROW_HOLDOFF_S (in-memory on purpose — a
        # failed-over supervisor growing a little early is harmless).
        self._last_resize: dict = {}

    # ---- helpers ----

    def prune_crash_backoff(self, key: str) -> None:
        """Drop crash-loop state for exactly this job's replicas.

        Exact replica-name structure match (``<key>-<type>-<index>``),
        NOT a string prefix: job ``default/train`` finishing must not
        also purge ``default/train-2``'s streak (the same trap
        _reset_status_dir documents). Called on job finish AND by
        Supervisor.delete_job — a same-name resubmission starts with a
        clean slate either way."""
        pat = re.compile(
            re.escape(key)
            + r"-(?:"
            + "|".join(rt.value.lower() for rt in ReplicaType)
            + r")-\d+$"
        )
        for name in [n for n in self._crash_backoff if pat.fullmatch(n)]:
            del self._crash_backoff[name]

    @staticmethod
    def job_subdir(root: Optional[Path], key: str) -> Optional[str]:
        """``root/<ns>_<name>``, created. Safe: names are DNS-1123-validated,
        so the ``/``→``_`` flattening cannot collide."""
        if root is None:
            return None
        d = root / key_to_fs(key)
        d.mkdir(parents=True, exist_ok=True)
        return str(d)

    def _status_dir(self, key: str) -> Optional[str]:
        return self.job_subdir(self.status_root, key)

    def _checkpoint_dir(self, key: str) -> Optional[str]:
        """Per-job checkpoint dir. Deliberately survives restarts AND job
        deletion/resubmission — job-level resume is "rerun the spec against
        the existing checkpoint dir" (SURVEY.md §5 "Checkpoint / resume");
        ``delete_job(purge_artifacts=True)`` reclaims it."""
        return self.job_subdir(self.checkpoint_root, key)

    def _trace_dir(self, job: TPUJob, key: str) -> Optional[str]:
        """Per-job span-file dir to inject, or None (tracing off for this
        job). On when the spec opts in OR the supervisor process itself
        is traced — global tracing traces the whole fleet."""
        from .. import obs

        ob = job.spec.observability
        if (ob is not None and ob.trace) or obs.trace_enabled():
            return self.job_subdir(self.trace_root, key)
        return None

    def begin_pass(self) -> None:
        """Start a supervisor sync pass. Resets the priority reservation
        (slots claimed by held higher-priority gangs — the supervisor syncs
        jobs in priority order, so a later lower-priority job cannot steal
        capacity a pending gang is waiting for) and computes queue usage
        once for the whole pass.

        A gang that can NEVER fit keeps its reservation and starves lower
        classes — the same behavior as a volcano PodGroup pending forever;
        the Unschedulable event is the operator's signal.
        """
        self._pass_reservations = {}
        self._pass_held = {}
        self._in_pass = True
        self._pass_queue_used = (
            self._compute_queue_usage() if self.queue_slots is not None else None
        )

    def end_pass(self) -> Optional[dict]:
        """Close a supervisor pass: solo syncs (foreground ``wait()``) must
        not admit against the pass's stale reservations or queue cache.
        Returns the pass's final {queue: device-slot usage} (None when
        queues are unconfigured) so the caller can reuse the accounting
        instead of rescanning every job."""
        self._in_pass = False
        return self._pass_queue_used

    def _compute_queue_usage(self) -> dict:
        """{queue: active device-slot usage} over every job in the store —
        the ONE implementation of queue accounting (begin_pass caches it
        for a pass; solo syncs compute it fresh)."""
        used: dict = {}
        for key in self.store.keys():
            job = self.store.get(key)
            if job is None:
                continue
            q = job.spec.run_policy.scheduling_policy.queue or "default"
            n = sum(h.slots for h in self.runner.list_for_job(key) if h.is_active())
            if n:
                used[q] = used.get(q, 0) + n
        return used

    def _queue_free(self, job: TPUJob, key: str) -> Optional[int]:
        """Free replica slots in the job's queue (volcano queue analog):
        queue capacity minus active replicas of ALL jobs naming that queue.
        None = queues unconfigured or this queue unlisted (unbounded)."""
        if self.queue_slots is None:
            return None
        qname = job.spec.run_policy.scheduling_policy.queue or "default"
        cap = self.queue_slots.get(qname)
        if cap is None:
            return None
        if self._in_pass and self._pass_queue_used is not None:
            used = self._pass_queue_used.get(qname, 0)
        else:
            # Solo sync (foreground run): compute fresh.
            used = self._compute_queue_usage().get(qname, 0)
        return max(0, cap - used)

    def _sync_suspended(self, job: TPUJob, key: str, now: float) -> bool:
        """Hold a suspended job: kill live replicas, keep the job object.

        The deadline clock resets (start_time cleared) so a later resume
        gets its full activeDeadlineSeconds — k8s suspend semantics.
        """
        self._delete_replicas(
            h for h in self.runner.list_for_job(key) if h.is_active()
        )
        if not job.has_condition(ConditionType.SUSPENDED):
            job.set_condition(
                ConditionType.SUSPENDED, reason="TPUJobSuspended",
                message=f"TPUJob {key} is suspended.", now=now,
            )
            self.events.normal(key, "TPUJobSuspended", f"TPUJob {key} is suspended.")
        if job.status.start_time is not None:
            job.status.start_time = None
            job.touch()
        update_replica_statuses(job, self.runner.list_for_job(key))
        self.store.update(job)
        return True

    def restart_world(
        self,
        job: TPUJob,
        key: str,
        handles: List[ReplicaHandle],
        reason: str,
        message: str,
        now: Optional[float] = None,
        warning: bool = True,
    ) -> None:
        """Tear down the whole gang for a re-rendezvous: delete every
        replica, spend one restart, set RESTARTING, record the event. The
        ONE implementation shared by failure restarts, elastic grow-back,
        and manual scale."""
        self._invalidate_resize(job, key)
        self._delete_replicas(handles)
        job.status.restart_count += 1
        self.metrics.jobs_restarted.inc()
        job.set_condition(
            ConditionType.RESTARTING, reason=reason, message=message, now=now
        )
        (self.events.warning if warning else self.events.normal)(key, reason, message)

    def held_gangs(self) -> dict:
        """Gangs held Unschedulable this pass: {key: (min_needed, priority)}
        — consumed by the supervisor's optional preemption step."""
        return dict(self._pass_held)

    def preempt_world(
        self,
        job: TPUJob,
        key: str,
        handles: List[ReplicaHandle],
        preemptor_key: str,
        now: Optional[float] = None,
    ) -> None:
        """Evict a lower-priority job's world for a pending gang (volcano
        ``preempt``). Unlike restart_world this does NOT spend the victim's
        restart/backoff budget — preemption is the cluster's choice, not
        the job's failure — so priority churn can never fail a victim."""
        self._invalidate_resize(job, key)
        self._delete_replicas(handles)
        self.metrics.jobs_preempted.inc()
        msg = (
            f"world preempted by higher-priority {preemptor_key}; "
            "will relaunch when capacity frees."
        )
        job.set_condition(
            ConditionType.RESTARTING, reason="TPUJobPreempted", message=msg, now=now
        )
        self.events.warning(key, "TPUJobPreempted", msg)

    def _invalidate_resize(self, job: TPUJob, key: str) -> None:
        """A whole-world teardown (restart, preemption) obsoletes any
        in-flight resize: the relaunched world is defined by its injected
        environment again. Clear the record AND zero the fenced
        generation — leaving the generation set with no record would make
        :meth:`_ensure_resize_record` resurrect the dead resize after a
        supervisor failover."""
        sd = self._status_dir(key)
        if sd is not None:
            clear_resize_record(sd)
        if job.status.resize_generation:
            job.status.resize_generation = 0
            job.touch()

    def _delete_replicas(self, handles) -> None:
        """Teardown accounting in one place: batch delete (one shared
        kill-escalation for the whole world) + metric per replica."""
        names = [h.name for h in handles]
        self.runner.delete_many(names)
        if names:
            self.metrics.replicas_deleted.inc(len(names))

    def _slots_minus_reserved(self, key: str) -> Optional[int]:
        """Free runner slots, excluding capacity claimed by OTHER held
        gangs in the current pass (a job's own claim never blocks it)."""
        slots = self.runner.schedulable_slots()
        if slots is not None and self._in_pass:
            reserved_others = sum(
                v for k2, v in list(self._pass_reservations.items()) if k2 != key
            )
            slots = max(0, slots - reserved_others)
        return slots

    def _fail_job(self, job: TPUJob, key: str, reason: str, message: str, now: float):
        job.set_condition(
            ConditionType.FAILED, reason=reason, message=message, now=now
        )
        if job.status.completion_time is None:
            job.status.completion_time = now
        self.events.warning(key, reason, message)
        self.metrics.jobs_failed.inc()

    def _cleanup_after_finish(self, job: TPUJob, key: str) -> None:
        """Apply CleanPodPolicy, drop the gang group and expectations.

        Reference: deletePodsAndServices/cleanupPyTorchJob (SURVEY.md §2
        "Job lifecycle / cleanup"). Idempotent.
        """
        policy = job.spec.run_policy.clean_pod_policy or CleanPodPolicy.RUNNING
        handles = self.runner.list_for_job(key)
        if policy != CleanPodPolicy.NONE:
            self._delete_replicas(
                h
                for h in handles
                # RUNNING leaves finished replicas' records/logs in place.
                if not (policy == CleanPodPolicy.RUNNING and not h.is_active())
            )
        self.gang.delete_group(key)
        self.expectations.delete_expectations(key)
        self._unschedulable_warned.discard(key)
        self._pass_reservations.pop(key, None)
        self.prune_crash_backoff(key)

    def _reset_status_dir(self, key: str) -> None:
        """Clear a prior incarnation's status reports (and their scan
        offsets) at job creation. Restarts within one incarnation keep the
        dir — their reports are still this job's."""
        if self.status_root is None:
            return
        from .progress import job_status_dir

        d = job_status_dir(self.status_root, key)
        if d.is_dir():
            import shutil

            shutil.rmtree(d, ignore_errors=True)
        # Parent-dir comparison, not a string prefix: "default_train" must
        # not also purge "default_train2"'s offsets.
        for p in [p for p in self._scan_offsets if Path(p).parent == d]:
            del self._scan_offsets[p]

    def _scan_first_step(self, job: TPUJob, key: str) -> None:
        """Pick up workload status reports: first-training-step records
        (the schedule-to-first-step latency probe, BASELINE.json:2) plus
        failure-path telemetry — skipped-corrupt-checkpoint and injected
        -stall records — folded into job events so `tpujob describe`
        shows the failure story, not just the recovery's outcome.

        Incremental per-file offsets keep the per-pass cost O(new
        bytes), so the scan runs every pass (not only until the first
        step is seen)."""
        if self.status_root is None:
            return
        from .progress import job_status_dir

        d = job_status_dir(self.status_root, key)
        import os as _os

        try:
            entries = [
                (Path(e.path), e.stat().st_size)
                for e in _os.scandir(d)
                if e.name.endswith(".jsonl")
            ]
        except OSError:
            return
        earliest = None
        for p, size in entries:
            # Incremental tail read: workloads append per-step records, so a
            # full re-parse every 100ms sync would be O(steps²) over a run.
            # The stat gate skips even the open() when nothing was appended.
            offset = self._scan_offsets.get(p, 0)
            if size <= offset:
                continue
            try:
                with p.open("rb") as f:
                    f.seek(offset)
                    chunk = f.read()
            except OSError:
                continue
            if not chunk:
                continue
            # Only consume complete lines; a partially-written record stays
            # for the next pass.
            last_nl = chunk.rfind(b"\n")
            if last_nl < 0:
                continue
            self._scan_offsets[p] = offset + last_nl + 1
            for line in chunk[: last_nl + 1].splitlines():
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                event = rec.get("event")
                if event == "first_step" and job.status.first_step_time is None:
                    ts = float(rec.get("ts", 0.0))
                    # Defense in depth vs stale files (e.g. a daemon restart
                    # loses scan offsets): a first step cannot precede this
                    # incarnation's submission.
                    if job.status.submit_time is not None and ts < job.status.submit_time:
                        continue
                    if earliest is None or ts < earliest:
                        earliest = ts
                elif event == "checkpoint_corrupt":
                    fb = rec.get("fallback")
                    self.events.warning(
                        key, "CheckpointCorrupt",
                        f"replica skipped corrupt checkpoint step "
                        f"{rec.get('step')}"
                        + (
                            f"; restoring from step {fb} or older."
                            if fb is not None
                            else "; no older step available."
                        ),
                    )
                elif event == "fault_stall":
                    self.events.warning(
                        key, "FaultInjected",
                        f"replica stalled {rec.get('seconds')}s at "
                        f"{rec.get('site', 'rendezvous')} (fault plan).",
                    )
                elif event == "rendezvous_join":
                    # Worker-side join latency rides the status channel
                    # into the live /metrics histogram (the supervisor
                    # cannot time a join it does not perform).
                    try:
                        self.metrics.rendezvous_join_seconds.observe(
                            float(rec.get("seconds", 0.0))
                        )
                    except (TypeError, ValueError):
                        pass
                elif event == "resize_join":
                    # Survivors confirming the resized membership — the
                    # resize history in `tpujob why` and the bench's
                    # duplicate-rank check both read these.
                    self.events.normal(
                        key, "ElasticResizeJoined",
                        f"replica {Path(p).stem} joined resized world: "
                        f"generation {rec.get('generation')}, rank "
                        f"{rec.get('rank')}/{rec.get('world_size')}.",
                    )
                elif event == "resize_evicted":
                    self.events.normal(
                        key, "ElasticResizeEvicted",
                        f"replica {Path(p).stem} fenced out of resized "
                        f"world (generation {rec.get('generation')}); "
                        "exited cleanly.",
                    )
        if earliest is not None and job.status.first_step_time is None:
            job.status.first_step_time = earliest
            job.touch()

    # ---- the core sync ----

    def key_lock(self, key: str) -> threading.RLock:
        """The per-key mutex; also taken by supervisor delete/scale so a
        teardown can't interleave with an in-flight sync of the same job.
        Reentrant: supervisor flows nest it (apply → submit → stale-reap
        delete_job all guard the same key)."""
        with self._key_locks_guard:
            return self._key_locks.setdefault(key, threading.RLock())

    def gc_key_locks(self, live_keys) -> None:
        """Retire locks of keys no longer in the store (a daemon with
        high job churn would otherwise leak one lock per key ever seen).
        Only uncontended locks are dropped — ``acquire(blocking=False)``
        proves no other thread holds it at pop time; popping a HELD lock
        would let a concurrent key_lock() mint a second one and race the
        holder (the reason the old per-delete drop_key_lock is gone).
        Call from a thread that holds none of them (the daemon loop)."""
        with self._key_locks_guard:
            for key in [k for k in self._key_locks if k not in live_keys]:
                lock = self._key_locks[key]
                if lock.acquire(blocking=False):
                    try:
                        self._key_locks.pop(key, None)
                    finally:
                        lock.release()

    def sync(self, key: str, now: Optional[float] = None) -> bool:
        """One reconcile pass. Returns True if the job still needs syncing."""
        from .. import obs

        t0 = time.perf_counter()
        with obs.span("reconcile", cat="supervisor", job=key):
            with self.key_lock(key):
                result = self._sync_locked(key, now)
        # Pooled across jobs (a per-job label would mint one series per
        # key ever seen); the distribution answers "is any reconcile
        # slow", the trace answers "which one".
        self.metrics.reconcile_seconds.observe(time.perf_counter() - t0)
        return result

    def _sync_locked(self, key: str, now: Optional[float]) -> bool:
        now = time.time() if now is None else now
        job = self.store.get(key)
        if job is None:
            return False
        set_defaults(job)

        if job.is_finished():
            self._cleanup_after_finish(job, key)
            self.store.update(job)
            return False

        # First observation → Created condition (reference: first sync sets
        # JobCreated and emits an Event).
        if job.get_condition(ConditionType.CREATED) is None:
            job.set_condition(
                ConditionType.CREATED, reason="TPUJobCreated",
                message=f"TPUJob {key} is created.", now=now,
            )
            self.events.normal(key, "TPUJobCreated", f"TPUJob {key} is created.")
            self.metrics.jobs_created.inc()
            # A fresh incarnation must not inherit the previous run's status
            # reports: a stale first_step record from a deleted+resubmitted
            # job with this key would yield a bogus (even negative)
            # schedule-to-first-step latency.
            self._reset_status_dir(key)

        # Suspend (reference: training-operator RunPolicy.suspend): tear
        # down any live world, mark Suspended, and wait for a resume.
        if job.spec.run_policy.suspend:
            return self._sync_suspended(job, key, now)
        if job.has_condition(ConditionType.SUSPENDED):
            job.set_condition(
                ConditionType.SUSPENDED, status=False,
                reason="TPUJobResumed", message=f"TPUJob {key} resumed.", now=now,
            )
            self.events.normal(key, "TPUJobResumed", f"TPUJob {key} resumed.")

        # ActiveDeadlineSeconds (reference: RunPolicy deadline → Failed).
        deadline = job.spec.run_policy.active_deadline_seconds
        if (
            deadline is not None
            and job.status.start_time is not None
            and now - job.status.start_time > deadline
        ):
            self._fail_job(
                job, key, "DeadlineExceeded",
                f"TPUJob {key} exceeded activeDeadlineSeconds={deadline}.", now,
            )
            self._cleanup_after_finish(job, key)
            self.store.update(job)
            return False

        if not self._in_pass:
            # Solo sync (foreground wait, tests): poll process liveness
            # here. Inside a supervisor pass the runner was synced ONCE
            # for the whole pass — N jobs must not trigger N /proc polls.
            self.runner.sync()
        handles = self.runner.list_for_job(key)
        # The template is the source of truth for a replica's device-slot
        # weight: heal records written before the weight existed (adopted
        # from an older supervisor) or with a stale value. Persisted by
        # the runner so a later restart adopts the corrected weight.
        for h in handles:
            rt_spec = job.spec.replica_specs.get(h.replica_type)
            if rt_spec is not None:
                w = replica_slots(rt_spec.template)
                if h.slots != w:
                    self.runner.set_slots(h.name, w)
        self._scan_first_step(job, key)
        if (
            job.spec.elastic_policy is not None
            and job.status.resize_generation > 0
        ):
            self._ensure_resize_record(job, key, handles)

        # ---- completion: job Succeeded ⇔ Master succeeded (status.go) ----
        master = master_handle(handles)
        if master is not None and master.phase == ReplicaPhase.SUCCEEDED:
            job.set_condition(
                ConditionType.SUCCEEDED, reason="TPUJobSucceeded",
                message=f"TPUJob {key} successfully completed.", now=now,
            )
            job.status.completion_time = now
            update_replica_statuses(job, handles)
            self.events.normal(key, "TPUJobSucceeded", f"TPUJob {key} successfully completed.")
            self.metrics.jobs_succeeded.inc()
            self._cleanup_after_finish(job, key)
            self.store.update(job)
            return False

        # ---- failure classification under restart policies ----
        restarts: List[ReplicaHandle] = []
        for h in handles:
            policy = (
                job.spec.replica_specs[h.replica_type].restart_policy
                or RestartPolicy.ON_FAILURE
            )
            if h.phase == ReplicaPhase.FAILED:
                self.metrics.replicas_failed.inc()
                action = classify_exit(policy, h.exit_code)
                if action == ACTION_FAIL_JOB:
                    self._fail_job(
                        job, key, "TPUJobFailed",
                        f"replica {h.name} failed with exit code {h.exit_code} "
                        f"(restartPolicy={policy.value}).", now,
                    )
                    update_replica_statuses(job, handles)
                    self._cleanup_after_finish(job, key)
                    self.store.update(job)
                    return False
                if action == ACTION_RESTART:
                    restarts.append(h)
                elif action == ACTION_NONE:
                    pass
            elif (
                h.phase == ReplicaPhase.SUCCEEDED
                and h.replica_type != ReplicaType.MASTER
                and policy == RestartPolicy.ALWAYS
            ):
                # Always restarts even successful workers (pod restartPolicy
                # Always semantics) — workers live until the master finishes.
                restarts.append(h)

        if restarts:
            return self._handle_restarts(job, key, handles, restarts, now)

        # ---- create missing replicas ----
        if not self.expectations.satisfied(key):
            self.store.update(job)
            return True

        missing = []
        for rtype, rs in job.spec.replica_specs.items():
            for index in self._desired_indices(job, key, rtype):
                if self.runner.get(replica_name(key, rtype, index)) is None:
                    missing.append((rtype, index))
        # replica_specs preserves user YAML key order, which may list Worker
        # before Master. Partial gang admission and elastic shrink both rely
        # on the Master heading the admitted prefix (a worker-only world
        # blocks at rendezvous forever, and the shrink arithmetic assumes
        # "master admitted first") — enforce it with a stable sort.
        missing.sort(key=lambda mi: mi[0] != ReplicaType.MASTER)

        if missing:
            # Crash-loop backoff gate: while ANY missing replica is
            # inside its respawn delay, hold the WHOLE job's creation
            # (partial creation would break the master-first gang
            # prefix); the poll loop retries next pass.
            held = max(
                (
                    self._crash_backoff[replica_name(key, rt, i)][1] - now
                    for rt, i in missing
                    if replica_name(key, rt, i) in self._crash_backoff
                ),
                default=0.0,
            )
            if held > 0:
                self.events.warning(
                    key, "CrashLoopBackOff",
                    "delaying respawn after repeated quick failures "
                    "(exponential backoff, capped at "
                    f"{CRASH_BACKOFF_CAP_S:.0f}s).",
                )
                self.store.update(job)
                return True

        if missing:
            total = sum(self._desired_replicas(job, rt) for rt in job.spec.replica_specs)
            policy = job.spec.run_policy.scheduling_policy
            # minMember semantics: min_available (defaulted to total by
            # set_defaults) is the count that must fit at once; below-total
            # values allow a partial world that waits at rendezvous. Capped
            # at the CURRENT total: an elastic scale-down must not leave a
            # stale submit-time threshold that can never be met.
            min_avail = min(
                policy.min_available if policy.min_available is not None else total,
                total,
            )
            self.gang.sync_group(key, min_member=min_avail)
            active_now = sum(1 for h in handles if h.is_active())
            gang_on = self.gang.enabled and policy.gang
            min_needed = max(0, min_avail - active_now) if gang_on else 1
            min_needed = max(1, min(min_needed, len(missing)))
            # Capacity is counted in device SLOTS (replica_slots: a 4-chip
            # replica weighs 4), while minMember stays a MEMBER count —
            # converted here to the weight of the first min_needed missing
            # replicas (master first, deterministic order).
            weights = {
                rt: replica_slots(job.spec.replica_specs[rt].template)
                for rt in job.spec.replica_specs
            }
            missing_w = [weights[rt] for rt, _ in missing]
            min_needed_w = sum(missing_w[:min_needed])
            slots = self._slots_minus_reserved(key)
            queue_free = self._queue_free(job, key)
            budget = self.gang.admissible(
                sum(missing_w), min_needed_w, slots, queue_free
            )
            if budget <= 0:
                queue_bound = queue_free is not None and queue_free < min_needed_w and (
                    slots is None or queue_free <= slots
                )
                if key not in self._unschedulable_warned:
                    self._unschedulable_warned.add(key)
                    where = (
                        f"queue '{policy.queue or 'default'}'"
                        if queue_bound
                        else "the available capacity"
                    )
                    self.events.warning(
                        key, "Unschedulable",
                        f"gang needs {min_needed_w} device slot(s) at once "
                        f"in {where}; holding replicas "
                        f"(min_available={min_avail} of {total} members).",
                    )
                # Reserve this gang's demand against lower-priority jobs
                # synced later in the pass.
                if self._in_pass:
                    self._pass_reservations[key] = sum(missing_w)
                    if not queue_bound:
                        # Only slot-bound holds may preempt: evicting
                        # other jobs' worlds cannot lift a QUEUE cap.
                        self._pass_held[key] = (min_needed_w, policy.priority)
                self.store.update(job)
                return True
            self._unschedulable_warned.discard(key)
            # Largest prefix of missing replicas whose weight fits budget
            # (>= the min_needed prefix, guaranteed by admissible()).
            n_admit, acc = 0, 0
            for w in missing_w:
                if acc + w > budget:
                    break
                acc += w
                n_admit += 1
            # Elastic capacity adaptation (torchelastic rendezvous-min
            # semantics): rather than launching a partial world that blocks
            # at rendezvous, SHRINK the desired world to what was admitted
            # (>= master + min_replicas, guaranteed by the admission floor)
            # and run it; _maybe_grow_elastic restores it as capacity frees.
            if (
                job.spec.elastic_policy is not None
                and gang_on
                and not handles
                and n_admit < len(missing)
            ):
                workers = job.spec.replica_specs.get(ReplicaType.WORKER)
                if workers is not None and n_admit - 1 >= (
                    job.spec.elastic_policy.min_replicas
                ):
                    workers.replicas = n_admit - 1  # master admitted first
                    job.touch()
                    msg = (
                        f"elastic launch shrunk to {workers.replicas} "
                        f"worker(s) to fit available capacity (target "
                        f"{job.metadata.annotations.get(ELASTIC_TARGET_ANNOTATION)})."
                    )
                    self.events.warning(key, "ElasticScaledDown", msg)
                    missing = [
                        (rt, i)
                        for rt in job.spec.replica_specs
                        for i in self._desired_indices(job, key, rt)
                        if self.runner.get(replica_name(key, rt, i)) is None
                    ]
                    missing.sort(key=lambda mi: mi[0] != ReplicaType.MASTER)
                    missing_w = [weights[rt] for rt, _ in missing]
            if self._in_pass:
                if n_admit < len(missing):
                    # Stragglers of a partially-admitted gang keep their claim.
                    self._pass_reservations[key] = sum(missing_w[n_admit:])
                else:
                    self._pass_reservations.pop(key, None)
            missing = missing[:n_admit]
            if self._in_pass and self._pass_queue_used is not None:
                qname = policy.queue or "default"
                self._pass_queue_used[qname] = self._pass_queue_used.get(
                    qname, 0
                ) + sum(missing_w[:n_admit])
            # Auto-port jobs get a freshly-probed coordinator port for each
            # new world (first launch or gang restart): probing at spawn
            # time keeps the free-probe → coordinator-bind window tiny, and
            # a fresh port per gang restart dodges TIME_WAIT on the old one.
            if (
                job.metadata.annotations.get(AUTO_PORT_ANNOTATION) == "true"
                and not handles
            ):
                from .supervisor import _find_free_port

                job.spec.port = _find_free_port()
                job.touch()
            status_dir = self._status_dir(key)
            checkpoint_dir = self._checkpoint_dir(key)
            trace_dir = self._trace_dir(job, key)
            num_processes = sum(
                self._desired_replicas(job, rt) for rt in job.spec.replica_specs
            )
            # In-place resize in effect: new creations (promoted spares, a
            # mid-failover recreate) join the RESIZED world — rank from the
            # record's compacted map (index-derived ranks are wrong once
            # survivor indices are sparse), the generation's coordinator,
            # and the record's world size.
            resize_rec = None
            if (
                job.spec.elastic_policy is not None
                and job.status.resize_generation > 0
                and status_dir is not None
            ):
                resize_rec = read_resize_record(status_dir)
                if resize_rec is not None and resize_rec.get(
                    "generation"
                ) != job.status.resize_generation:
                    resize_rec = None
            serve_job = (
                job.spec.serving is not None and self.serve_root is not None
            )
            self.expectations.expect_creations(key, len(missing), now=now)
            try:
                for rtype, index in missing:
                    spool_dir = None
                    if serve_job:
                        # The router derives the identical path from the
                        # runner handle (serving/router.replica_spool_dir
                        # — layout IS the contract).
                        from ..serving.router import replica_spool_dir

                        sd = replica_spool_dir(
                            self.serve_root, key, rtype.value, index
                        )
                        sd.mkdir(parents=True, exist_ok=True)
                        spool_dir = str(sd)
                        if job.spec.serving.transport == "shmring":
                            # Pre-arm the ring pair at SPAWN instead of
                            # the router's first dispatch: the engine
                            # attaches the moment it starts, so the
                            # first request rides the memory tier (the
                            # ~1.1s first-second TTFT p99 warm-up spike
                            # was requests spilling to the file path
                            # while the rings armed).
                            from ..serving.shmring import prearm_rings

                            try:
                                prearm_rings(sd)
                            except OSError:
                                pass  # router creates them on dispatch
                    rank = None
                    coord_port = None
                    resize_gen = None
                    world_n = num_processes
                    if resize_rec is not None:
                        rank = resize_rec.get("ranks", {}).get(
                            member_id(rtype.value, index)
                        )
                        _, _, p_str = str(
                            resize_rec.get("coordinator", "")
                        ).rpartition(":")
                        if p_str.isdigit():
                            coord_port = int(p_str)
                        resize_gen = int(resize_rec.get("generation", 0))
                        world_n = int(
                            resize_rec.get("world_size", num_processes)
                        )
                    env = build_cluster_env(
                        job, rtype, index,
                        num_processes=world_n,
                        coordinator_host=self.coordinator_host,
                        status_dir=status_dir,
                        checkpoint_dir=checkpoint_dir,
                        trace_dir=trace_dir,
                        spool_dir=spool_dir,
                        rank=rank,
                        coordinator_port=coord_port,
                        resize_generation=resize_gen,
                    )
                    self.runner.create(
                        key, rtype, index, job.spec.replica_specs[rtype].template, env
                    )
                    self.expectations.creation_observed(key)
                    self.metrics.replicas_created.inc()
                    self.events.normal(
                        key, "SuccessfulCreateReplica",
                        f"Created replica {replica_name(key, rtype, index)}.",
                    )
            except Exception as e:
                # The reference calls CreationObserved on create error:
                # un-launched expectations must not gate this job's syncs
                # for the full expectation timeout once the caller
                # recovers. Surface the failure as an event, then
                # propagate (the job retries on the next pass).
                self.expectations.delete_expectations(key)
                self.events.warning(
                    key, "FailedCreateReplica", f"replica create failed: {e}"
                )
                raise
            handles = self.runner.list_for_job(key)

        # ---- elastic grow-back toward the submitted target ----
        if self._maybe_grow_elastic(job, key, handles, now):
            self.store.update(job)
            return True

        # ---- Running condition ----
        master = master_handle(handles)
        if master is not None and master.phase == ReplicaPhase.RUNNING:
            if job.status.start_time is None:
                job.status.start_time = now
            if not job.has_condition(ConditionType.RUNNING):
                job.set_condition(
                    ConditionType.RUNNING, reason="TPUJobRunning",
                    message=f"TPUJob {key} is running.", now=now,
                )
                self.events.normal(key, "TPUJobRunning", f"TPUJob {key} is running.")
            # Hung-world detection (opt-in via annotation): a wedged
            # collective exits nothing, so liveness must come from the
            # heartbeat channel, with a deadline kill as the recovery.
            if self._maybe_kill_hung(job, key, handles, master, now):
                return not job.is_finished()

        update_replica_statuses(job, handles)
        self.store.update(job)
        return True

    # ---- hung-world detection ----

    @staticmethod
    def _hang_deadline_s(job: TPUJob) -> Optional[float]:
        raw = job.metadata.annotations.get(HANG_DEADLINE_ANNOTATION)
        if not raw:
            return None
        try:
            v = float(raw)
        except (TypeError, ValueError):
            return None
        return v if v > 0 else None

    def _last_heartbeat(self, job: TPUJob, key: str, master) -> float:
        """The newest liveness signal for the CURRENT world: latest
        progress heartbeat, first-step report, or — before any report —
        the master's spawn time (a fresh world gets one full deadline to
        produce its first beat; without this floor a restarted world
        would be re-killed instantly off the old world's stale file)."""
        candidates = [master.created_at or 0.0]
        if job.status.first_step_time is not None:
            candidates.append(job.status.first_step_time)
        if self.status_root is not None:
            from .progress import job_status_dir, read_latest_progress

            rec = read_latest_progress(job_status_dir(self.status_root, key))
            if rec is not None:
                candidates.append(float(rec.get("ts", 0.0)))
        return max(candidates)

    def _maybe_kill_hung(
        self, job: TPUJob, key: str, handles, master, now: float
    ) -> bool:
        """Deadline-kill a world whose heartbeats stopped. Returns True
        when it acted (restart spent, or job failed at the backoff
        limit) — the caller's pass is over for this job either way."""
        hang_s = self._hang_deadline_s(job)
        if hang_s is None:
            return False
        silent = now - self._last_heartbeat(job, key, master)
        if silent <= hang_s:
            return False
        backoff = job.spec.run_policy.backoff_limit
        if backoff is not None and job.status.restart_count + 1 > backoff:
            self._fail_job(
                job, key, "TPUJobHung",
                f"no heartbeat for {silent:.1f}s (deadline {hang_s:.0f}s) "
                f"and the backoff limit ({backoff}) is exhausted.", now,
            )
            update_replica_statuses(job, handles)
            self._cleanup_after_finish(job, key)
            self.store.update(job)
            return True
        msg = (
            f"no heartbeat for {silent:.1f}s (deadline {hang_s:.0f}s); "
            f"killing the hung world "
            f"(restart #{job.status.restart_count + 1})."
        )
        self.restart_world(
            job, key, [h for h in handles if h.is_active()],
            "TPUJobHung", msg, now=now,
        )
        update_replica_statuses(job, self.runner.list_for_job(key))
        self.store.update(job)
        return True

    def _desired_replicas(self, job: TPUJob, rtype: ReplicaType) -> int:
        return job.spec.replica_specs[rtype].replicas or 0

    def _desired_indices(self, job: TPUJob, key: str, rtype: ReplicaType) -> List[int]:
        """Which replica INDICES the desired count maps onto.

        Non-elastic (and the Master): dense ``range(count)``. Elastic
        workers: survivor indices stay SPARSE after an in-place resize
        (worker-2 keeps its name/logs/status file when worker-1 dies), so
        desired = the live indices capped at the count, topped up from the
        lowest indices with NO runner record at all — a FAILED or
        SUCCEEDED record still occupies its index (``runner.create``
        refuses to overwrite it, and an evicted replica's SUCCEEDED
        record is exactly what keeps it from being recreated). A
        SUCCEEDED replica also fills its SLOT, not just its index:
        completed work is never respawned at a fresh index (a new worker
        joining a world that is finishing would die into a restart)."""
        count = self._desired_replicas(job, rtype)
        if job.spec.elastic_policy is None or rtype == ReplicaType.MASTER:
            return list(range(count))
        recs = [
            h
            for h in self.runner.list_for_job(key)
            if h.replica_type == rtype
        ]
        live = sorted(h.index for h in recs if h.is_active())[:count]
        succeeded = sum(
            1
            for h in recs
            if not h.is_active()
            and h.phase == ReplicaPhase.SUCCEEDED
            and h.index not in live
        )
        want = max(len(live), count - succeeded)
        out = list(live)
        idx = 0
        while len(out) < want:
            if idx not in out and self.runner.get(
                replica_name(key, rtype, idx)
            ) is None:
                out.append(idx)
            idx += 1
        return sorted(out)

    # ---- elastic in-place resize ----

    def _latest_verified_step(self, key: str) -> Optional[int]:
        """Last sidecar-verified checkpoint step for this job — what a
        resized world repartitions from ("fenced, not torn": a crash
        mid-resize resumes from this same step)."""
        ckpt_dir = self._checkpoint_dir(key)
        if ckpt_dir is None:
            return None
        try:
            from ..checkpoint.integrity import latest_verified_step

            return latest_verified_step(ckpt_dir)
        except Exception as e:
            # Probe failure must be visible: a resize that silently sees
            # "no verified checkpoint" restarts the world from step 0.
            self.events.warning(
                key, "CheckpointProbeFailed",
                f"could not determine last verified step under "
                f"{ckpt_dir}: {e}",
            )
            return None

    def _ensure_resize_record(self, job: TPUJob, key: str, handles) -> None:
        """Failover heal for the resize contract. ``status.resize_generation``
        is the lease-fenced truth; ``resize.json`` is derived state. A
        supervisor that crashed between the store commit and the record
        write — or a new owner after failover — rewrites the SAME
        generation's record deterministically instead of minting a second
        resize (exactly-once)."""
        status_dir = self._status_dir(key)
        if status_dir is None:
            return
        rec = read_resize_record(status_dir)
        if rec is not None and rec.get("generation") == job.status.resize_generation:
            return
        # Membership := the same fill rule the create pass applies; dead
        # (FAILED) replicas still hold records, so they are excluded
        # automatically and listed as handled — a later re-observation of
        # the same deaths completes THIS generation instead of bumping.
        members = self._desired_indices(job, key, ReplicaType.WORKER)
        handled = sorted(
            h.name for h in handles if h.phase == ReplicaPhase.FAILED
        )
        write_resize_record(
            status_dir,
            build_resize_record(
                generation=job.status.resize_generation,
                ranks=reassign_ranks(members),
                coordinator=f"{self.coordinator_host}:{job.spec.port or 23456}",
                restore_step=self._latest_verified_step(key),
                handled=handled,
            ),
        )
        self.events.normal(
            key, "ElasticResizeHealed",
            f"rewrote resize record for generation "
            f"{job.status.resize_generation} after supervisor failover.",
        )

    def _resize_world(
        self,
        job: TPUJob,
        key: str,
        handles: List[ReplicaHandle],
        restarts: List[ReplicaHandle],
        decision,
        now: float,
    ) -> bool:
        """Shrink (or spare-backfill) the gang IN PLACE: survivors keep
        running and re-join at the new world size via the resize record —
        no teardown, no restart spent, no scheduler round trip.

        Commit order is the exactly-once story: (1) bump
        ``status.resize_generation`` through the lease-fenced store — the
        commit point; (2) write the resize record (derived state —
        :meth:`_ensure_resize_record` rewrites it after a crash);
        (3) delete the dead replicas' records. A failover replay that
        re-observes the same deaths finds them ⊆ the record's ``handled``
        set and completes cleanup without a second bump."""
        from .. import obs

        status_dir = self._status_dir(key)
        dead_names = sorted(h.name for h in restarts)
        rec = read_resize_record(status_dir) if status_dir is not None else None
        if (
            job.status.resize_generation > 0
            and rec is not None
            and rec.get("generation") == job.status.resize_generation
            and set(dead_names) <= set(rec.get("handled", ()))
        ):
            # Failover replay: this generation already consumed exactly
            # these deaths — finish its cleanup, do NOT mint another.
            self._delete_replicas(restarts)
            update_replica_statuses(job, self.runner.list_for_job(key))
            self.store.update(job)
            return True

        with obs.span(
            "resize", cat="supervisor", job=key,
            generation=job.status.resize_generation + 1,
        ):
            elastic = job.spec.elastic_policy
            workers = job.spec.replica_specs.get(ReplicaType.WORKER)
            survivors = list(decision.survivors)
            # Hot spares: backfill dead seats from warm standbys — the
            # promotion is just a create at the freed index, which the
            # runner hands to a pre-imported standby (no cold spawn).
            promote = 0
            if elastic.hot_spares > 0:
                ready = getattr(self.runner, "standby_ready", lambda: 0)()
                slots = self._slots_minus_reserved(key)
                room = (
                    len(decision.dead_workers)
                    if slots is None
                    else min(len(decision.dead_workers), slots)
                )
                promote = max(0, min(ready, room))
            members = list(survivors)
            if promote:
                members += [
                    i for i in decision.dead_workers if i not in members
                ][:promote]
            members.sort()
            ranks = reassign_ranks(members)

            # A fresh coordinator port per generation (auto-port jobs):
            # the transport-layer half of the stale-straggler fence — a
            # zombie from the old generation cannot even reach the new
            # world's rendezvous.
            if job.metadata.annotations.get(AUTO_PORT_ANNOTATION) == "true":
                from .supervisor import _find_free_port

                job.spec.port = _find_free_port()
            coordinator = f"{self.coordinator_host}:{job.spec.port or 23456}"
            restore_step = self._latest_verified_step(key)

            # (1) commit point: the generation bump and the new desired
            # count ride the lease-fenced store together.
            job.status.resize_generation += 1
            if workers is not None:
                workers.replicas = len(members)
            job.touch()
            self.store.update(job)
            # (2) the survivors' re-join contract.
            if status_dir is not None:
                write_resize_record(
                    status_dir,
                    build_resize_record(
                        generation=job.status.resize_generation,
                        ranks=ranks,
                        coordinator=coordinator,
                        restore_step=restore_step,
                        handled=dead_names,
                        ts=now,
                    ),
                )
            # (3) retire the dead; the create pass backfills promoted
            # seats at the freed indices next sync.
            self._delete_replicas(restarts)

        self._last_resize[key] = now
        self.metrics.elastic_resizes.inc()
        world = len(members) + 1  # + master
        if promote:
            msg = (
                f"in-place resize (generation "
                f"{job.status.resize_generation}): {decision.reason}; "
                f"promoted {promote} hot spare(s), world size {world} "
                f"(restore step {restore_step})."
            )
            self.events.normal(key, "ElasticSparePromoted", msg)
        else:
            msg = (
                f"in-place resize (generation "
                f"{job.status.resize_generation}): {decision.reason}; "
                f"world shrinks to {world} "
                f"(restore step {restore_step}, no restart spent)."
            )
            self.events.warning(key, "ElasticScaledDown", msg)
        update_replica_statuses(job, self.runner.list_for_job(key))
        self.store.update(job)
        return True

    def _maybe_grow_elastic(
        self, job: TPUJob, key: str, handles: List[ReplicaHandle], now: float
    ) -> bool:
        """Grow a capacity-shrunk elastic world back toward its submitted
        target when slots free up (the reverse of ElasticScaledDown).

        Growth is a membership change: the whole gang re-rendezvouses, so
        it spends one restart from the elastic budget — and is skipped when
        the budget is exhausted (growth must never fail the job).
        """
        elastic = job.spec.elastic_policy
        if elastic is None:
            return False
        # Post-resize holdoff: let the shrunken world make progress before
        # spending a restart to chase the submitted target again.
        if now - self._last_resize.get(key, 0.0) < RESIZE_GROW_HOLDOFF_S:
            return False
        workers = job.spec.replica_specs.get(ReplicaType.WORKER)
        if workers is None:
            return False
        try:
            target = int(
                job.metadata.annotations.get(ELASTIC_TARGET_ANNOTATION, "")
            )
        except ValueError:
            return False
        # The annotation is user-writable: never grow past the validated
        # elastic bound.
        target = min(target, elastic.max_replicas)
        cur = workers.replicas or 0
        if target <= cur:
            return False
        backoff = job.spec.run_policy.backoff_limit
        if job.status.restart_count + 1 > elastic.max_restarts or (
            # Growth must never fail the job NOR spend the failure budget
            # down to the point where the next real failure kills it: after
            # growing, at least one failure-restart must remain.
            backoff is not None
            and job.status.restart_count + 2 > backoff
        ):
            return False
        # Only grow a stable, fully-running world (not one mid-launch).
        desired_total = sum(
            self._desired_replicas(job, rt) for rt in job.spec.replica_specs
        )
        master = master_handle(handles)
        if (
            len([h for h in handles if h.is_active()]) < desired_total
            or master is None
            or master.phase != ReplicaPhase.RUNNING
        ):
            return False
        slots = self._slots_minus_reserved(key)
        queue_free = self._queue_free(job, key)
        # Free capacity is in device slots; one extra worker costs its
        # replica weight.
        w = replica_slots(workers.template)
        bounds = [b // w for b in (slots, queue_free) if b is not None]
        grow = min([target - cur] + bounds) if bounds else target - cur
        if grow <= 0:
            return False
        workers.replicas = cur + grow
        job.touch()
        msg = (
            f"elastic grow-back to {workers.replicas} worker(s) toward "
            f"target {target} (restart #{job.status.restart_count + 1})."
        )
        # Membership change → tear down the world; next sync relaunches it
        # at the new size (same path as Supervisor.scale).
        self.restart_world(
            job, key, handles, "ElasticScaledUp", msg, now=now, warning=False
        )
        if self._in_pass:
            # The torn-down world's slots are spoken for: the grown gang
            # relaunches next sync. Without this claim, jobs synced later
            # in the pass steal the capacity and the restart was wasted.
            self._pass_reservations[key] = sum(
                self._desired_replicas(job, rt)
                * replica_slots(job.spec.replica_specs[rt].template)
                for rt in job.spec.replica_specs
            )
            if self._pass_queue_used is not None:
                qname = job.spec.run_policy.scheduling_policy.queue or "default"
                self._pass_queue_used[qname] = (
                    self._pass_queue_used.get(qname, 0) + grow * w
                )
        return True

    def _handle_restarts(
        self,
        job: TPUJob,
        key: str,
        handles: List[ReplicaHandle],
        restarts: List[ReplicaHandle],
        now: float,
    ) -> bool:
        """Respawn retryable replicas, enforcing backoff / elastic limits.

        Non-elastic: delete just the failed replicas; next sync recreates
        them (reference: "pod Failed + restartable → delete pod (respawn
        next sync)").

        Elastic: any membership change re-rendezvouses the whole gang — all
        replicas are torn down and recreated with a fresh world (SURVEY.md §5
        "Failure detection / elastic recovery").
        """
        # Record crash-loop state BEFORE the failed handles are deleted:
        # respawn (next sync's create pass) honors the delay.
        for h in restarts:
            if h.phase != ReplicaPhase.FAILED:
                continue
            uptime = (h.finished_at or now) - (h.created_at or now)
            streak, _ = self._crash_backoff.get(h.name, (0, 0.0))
            streak = 1 if uptime >= CRASH_RESET_UPTIME_S else streak + 1
            delay = (
                0.0
                if streak == 1
                else min(
                    CRASH_BACKOFF_CAP_S,
                    CRASH_BACKOFF_BASE_S * 2 ** (streak - 2),
                )
            )
            self._crash_backoff[h.name] = (streak, now + delay)

        elastic = job.spec.elastic_policy
        decision = None
        if elastic is not None:
            # Partial-gang vs whole-world: a death the gang can absorb
            # shrinks the world IN PLACE — no teardown, no restart spent,
            # no budget check (resize is recovery, not failure). Falls
            # through to the restart path when the coordinator died or
            # the survivors would dip below min_replicas.
            decision = classify_death(elastic, handles, restarts)
            if decision.action == RESIZE:
                return self._resize_world(
                    job, key, handles, restarts, decision, now
                )

        n_new_restarts = len(restarts)
        backoff = job.spec.run_policy.backoff_limit
        if backoff is not None and job.status.restart_count + n_new_restarts > backoff:
            self._fail_job(
                job, key, "BackoffLimitExceeded",
                f"TPUJob {key} has reached the specified backoff limit "
                f"({backoff}).", now,
            )
            update_replica_statuses(job, handles)
            self._cleanup_after_finish(job, key)
            self.store.update(job)
            return False

        if elastic is not None:
            if job.status.restart_count + 1 > elastic.max_restarts:
                self._fail_job(
                    job, key, "MaxRestartsExceeded",
                    f"TPUJob {key} exceeded elastic max_restarts "
                    f"({elastic.max_restarts}).", now,
                )
                update_replica_statuses(job, handles)
                self._cleanup_after_finish(job, key)
                self.store.update(job)
                return False
            # Gang re-rendezvous: tear down the whole world.
            why = decision.reason if decision is not None else "membership change"
            msg = (
                f"elastic re-rendezvous: {why} "
                f"(restart #{job.status.restart_count + 1})."
            )
            self.restart_world(job, key, handles, "TPUJobRestarting", msg, now=now)
            update_replica_statuses(job, self.runner.list_for_job(key))
            self.store.update(job)
            return True
        else:
            self._delete_replicas(restarts)
            job.status.restart_count += n_new_restarts
            self.metrics.jobs_restarted.inc(n_new_restarts)
            reason = "TPUJobRestarting"
            names = ", ".join(h.name for h in restarts)
            msg = f"restarting replica(s) {names} (restart #{job.status.restart_count})."

        job.set_condition(ConditionType.RESTARTING, reason=reason, message=msg, now=now)
        self.events.warning(key, reason, msg)
        update_replica_statuses(job, self.runner.list_for_job(key))
        self.store.update(job)
        return True
