"""Reconciler tests against the FakeRunner — the fake-clientset pattern
(SURVEY.md §4): build a job, run sync passes, assert on the runner's action
log and the job's conditions. Replica "execution" is simulated by setting
phases by hand and re-syncing; no processes, no TPU.
"""

from pytorch_operator_tpu.api import (
    CleanPodPolicy,
    ConditionType,
    ElasticPolicy,
    ReplicaPhase,
    ReplicaType,
    RestartPolicy,
)
from pytorch_operator_tpu.controller import (
    EventRecorder,
    FakeRunner,
    GangScheduler,
    JobStore,
    MetricsRegistry,
    Reconciler,
    replica_name,
)
from tests.testutil import new_job


def make_harness(capacity=None, gang_enabled=True):
    store = JobStore()
    runner = FakeRunner(capacity=capacity)
    events = EventRecorder()
    metrics = MetricsRegistry()
    rec = Reconciler(
        store=store,
        runner=runner,
        events=events,
        metrics=metrics,
        gang=GangScheduler(enabled=gang_enabled),
    )
    return store, runner, events, metrics, rec


class TestCreation:
    def test_creates_master_and_workers(self):
        store, runner, events, metrics, rec = make_harness()
        job = new_job(workers=2)
        key = store.add(job)
        rec.sync(key)
        created = [a for a in runner.actions if a[0] == "create"]
        assert len(created) == 3
        assert runner.get(replica_name(key, ReplicaType.MASTER, 0)) is not None
        assert runner.get(replica_name(key, ReplicaType.WORKER, 0)) is not None
        assert runner.get(replica_name(key, ReplicaType.WORKER, 1)) is not None
        assert metrics.replicas_created.get() == 3
        assert metrics.jobs_created.get() == 1

    def test_created_condition_and_event(self):
        store, runner, events, _, rec = make_harness()
        key = store.add(new_job())
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.CREATED)
        assert any(e.reason == "TPUJobCreated" for e in events.for_job(key))

    def test_env_injection(self):
        """The SetClusterSpec contract: rank/world-size + the jax.distributed
        coordinates. A job that asks for no device gets no platform pin and
        none of libtpu's variables."""
        store, runner, _, _, rec = make_harness()
        job = new_job(name="envjob", workers=2)
        key = store.add(job)
        rec.sync(key)
        menv = runner.envs[replica_name(key, ReplicaType.MASTER, 0)]
        assert menv["RANK"] == "0"
        assert menv["WORLD_SIZE"] == "3"
        # fixture omitted the port → auto-allocated; env must match the spec
        assert menv["MASTER_PORT"] == str(store.get(key).spec.port)
        assert menv["PYTHONUNBUFFERED"] == "1"
        assert menv["TPUJOB_NUM_PROCESSES"] == "3"
        assert menv["TPUJOB_COORDINATOR_ADDRESS"].endswith(
            f":{store.get(key).spec.port}"
        )
        w1 = runner.envs[replica_name(key, ReplicaType.WORKER, 1)]
        assert w1["RANK"] == "2"  # worker i → rank i+1
        assert w1["TPUJOB_PROCESS_ID"] == "2"
        assert w1["TPUJOB_REPLICA_TYPE"] == "Worker"
        for env in (menv, w1):
            assert "JAX_PLATFORMS" not in env
            assert not [k for k in env if k.startswith(("TPU_", "CLOUD_TPU"))]
            assert "PJRT_DEVICE" not in env
            assert "JAX_COMPILATION_CACHE_DIR" not in env

    def test_resubmission_does_not_inherit_stale_first_step(self, tmp_path):
        """Delete + resubmit under the same key must wipe the previous
        incarnation's status reports, else schedule-to-first-step latency
        goes negative (computed from the OLD run's first_step record)."""
        import json as _json
        import time as _time

        store = JobStore()
        runner = FakeRunner()
        rec = Reconciler(store=store, runner=runner, status_root=tmp_path / "status")
        key = store.add(new_job(name="stale", workers=0))
        rec.sync(key)
        # Old incarnation reports its first step, then is deleted.
        d = tmp_path / "status" / key.replace("/", "_")
        stale_ts = _time.time() - 3600
        (d / "Master-0.jsonl").write_text(
            _json.dumps({"event": "first_step", "ts": stale_ts}) + "\n"
        )
        rec.sync(key)
        assert store.get(key).status.first_step_time is None  # filtered: pre-submit
        store.delete(key)

        key = store.add(new_job(name="stale", workers=0))
        rec.sync(key)
        job = store.get(key)
        assert not (d / "Master-0.jsonl").exists()  # dir wiped at creation
        assert job.status.first_step_time is None
        # A report from THIS incarnation is picked up normally.
        d.mkdir(parents=True, exist_ok=True)
        now_ts = _time.time()
        (d / "Master-0.jsonl").write_text(
            _json.dumps({"event": "first_step", "ts": now_ts}) + "\n"
        )
        rec.sync(key)
        job = store.get(key)
        assert job.status.first_step_time == now_ts
        assert job.status.first_step_time >= job.status.submit_time

    def test_device_env_from_resources(self):
        """``resources`` decides the platform and, for chips, libtpu's
        per-process variables — injected env is laid over the inherited
        one at spawn, so the pin beats an exported JAX_PLATFORMS=cpu."""
        from pytorch_operator_tpu.api.types import Resources

        def envs(resources, workers):
            store, runner, _, _, rec = make_harness()
            job = new_job(name="devjob", workers=workers)
            for rs in job.spec.replica_specs.values():
                rs.template.resources = resources
            key = store.add(job)
            rec.sync(key)
            port = store.get(key).spec.port
            names = [replica_name(key, ReplicaType.MASTER, 0)] + [
                replica_name(key, ReplicaType.WORKER, i) for i in range(workers)
            ]
            return [runner.envs[n] for n in names], port

        (cpu,), _ = envs(Resources(cpu_devices=4), 0)
        assert cpu["JAX_PLATFORMS"] == "cpu"
        assert cpu["XLA_FLAGS"].endswith("device_count=4")
        assert not [k for k in cpu if k.startswith("TPU_")]

        (one,), _ = envs(Resources(tpu_chips=1), 0)
        assert one["JAX_PLATFORMS"] == "tpu,cpu"
        assert one["TPU_VISIBLE_CHIPS"] == "0"
        assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert one["TPU_PROCESS_BOUNDS"] == "1,1,1"
        # Alone on its chips: nothing that tells libtpu about peers.
        assert "TPU_PROCESS_ADDRESSES" not in one
        assert "CLOUD_TPU_TASK_ID" not in one

        (four,), _ = envs(Resources(tpu_chips=4), 0)
        assert four["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert four["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,2,1"

        gang, port = envs(Resources(tpu_chips=1), 3)
        assert [e["TPU_VISIBLE_CHIPS"] for e in gang] == ["0", "1", "2", "3"]
        assert [e["CLOUD_TPU_TASK_ID"] for e in gang] == ["0", "1", "2", "3"]
        assert {e["TPU_PROCESS_BOUNDS"] for e in gang} == {"2,2,1"}
        addresses = ",".join(f"127.0.0.1:{port + 1 + i}" for i in range(4))
        assert {e["TPU_PROCESS_ADDRESSES"] for e in gang} == {addresses}
        assert [e["TPU_PROCESS_PORT"] for e in gang] == [
            str(port + 1 + i) for i in range(4)
        ]
        # The old route's variables are gone: libtpu reads them as the
        # slice's host list, and JAX never read PJRT_DEVICE.
        for e in gang:
            assert "TPU_WORKER_HOSTNAMES" not in e and "PJRT_DEVICE" not in e

    def test_no_duplicate_creation_on_resync(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=2))
        rec.sync(key)
        rec.sync(key)
        rec.sync(key)
        created = [a for a in runner.actions if a[0] == "create"]
        assert len(created) == 3

    def test_recreates_missing_replica(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1))
        rec.sync(key)
        # simulate lost record (no phase change): handle removed
        runner.remove_record(replica_name(key, ReplicaType.WORKER, 0))
        rec.sync(key)
        assert runner.get(replica_name(key, ReplicaType.WORKER, 0)) is not None


class TestRunningAndSuccess:
    def test_running_condition_when_master_runs(self):
        store, runner, events, _, rec = make_harness()
        key = store.add(new_job(workers=1))
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RUNNING)
        assert job.status.start_time is not None
        assert job.status.replica_statuses[ReplicaType.MASTER].active == 1
        assert job.status.replica_statuses[ReplicaType.WORKER].active == 1

    def test_master_success_means_job_success(self):
        store, runner, events, metrics, rec = make_harness()
        key = store.add(new_job(workers=1))
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        job = store.get(key)
        assert job.is_succeeded()
        assert job.status.completion_time is not None
        assert not job.has_condition(ConditionType.RUNNING)
        assert metrics.jobs_succeeded.get() == 1

    def test_worker_success_does_not_finish_job(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1))
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        job = store.get(key)
        assert not job.is_finished()
        assert job.status.replica_statuses[ReplicaType.WORKER].succeeded == 1

    def test_success_cleanup_running_policy_kills_workers(self):
        store, runner, _, metrics, rec = make_harness()
        key = store.add(new_job(workers=2, clean_pod_policy=CleanPodPolicy.RUNNING))
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        # workers were Running → deleted; master finished → record kept
        deleted = [a[1] for a in runner.actions if a[0] == "delete"]
        assert replica_name(key, ReplicaType.WORKER, 0) in deleted
        assert replica_name(key, ReplicaType.WORKER, 1) in deleted
        assert replica_name(key, ReplicaType.MASTER, 0) not in deleted

    def test_success_cleanup_none_policy_leaves_all(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, clean_pod_policy=CleanPodPolicy.NONE))
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        deleted = [a for a in runner.actions if a[0] == "delete"]
        assert deleted == []

    def test_success_cleanup_all_policy_removes_everything(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, clean_pod_policy=CleanPodPolicy.ALL))
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        deleted = [a[1] for a in runner.actions if a[0] == "delete"]
        assert len(deleted) == 2  # master record + running worker


class TestRestartPolicies:
    def _fail_worker(self, runner, key, exit_code):
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 0), ReplicaPhase.FAILED, exit_code
        )

    def test_on_failure_restarts(self):
        store, runner, events, metrics, rec = make_harness()
        key = store.add(new_job(workers=1, restart_policy=RestartPolicy.ON_FAILURE))
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        self._fail_worker(runner, key, 1)
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RESTARTING)
        assert not job.has_condition(ConditionType.RUNNING)
        assert job.status.restart_count == 1
        # next sync recreates the worker
        rec.sync(key)
        assert runner.get(replica_name(key, ReplicaType.WORKER, 0)) is not None
        assert metrics.jobs_restarted.get() == 1

    def test_never_fails_job(self):
        store, runner, _, metrics, rec = make_harness()
        key = store.add(new_job(workers=1, restart_policy=RestartPolicy.NEVER))
        rec.sync(key)
        runner.set_all_running(key)
        self._fail_worker(runner, key, 1)
        rec.sync(key)
        job = store.get(key)
        assert job.is_failed()
        assert metrics.jobs_failed.get() == 1

    def test_exit_code_permanent(self):
        """ExitCode policy: exit 1–127 = permanent failure."""
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, restart_policy=RestartPolicy.EXIT_CODE))
        rec.sync(key)
        runner.set_all_running(key)
        self._fail_worker(runner, key, 1)
        rec.sync(key)
        assert store.get(key).is_failed()

    def test_exit_code_retryable(self):
        """ExitCode policy: exit >=128 (e.g. SIGKILL=137) = retryable."""
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, restart_policy=RestartPolicy.EXIT_CODE))
        rec.sync(key)
        runner.set_all_running(key)
        self._fail_worker(runner, key, 137)
        rec.sync(key)
        job = store.get(key)
        assert not job.is_finished()
        assert job.has_condition(ConditionType.RESTARTING)
        assert job.status.restart_count == 1

    def test_always_restarts_succeeded_worker(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, restart_policy=RestartPolicy.ALWAYS))
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RESTARTING)
        rec.sync(key)
        assert runner.get(replica_name(key, ReplicaType.WORKER, 0)) is not None

    def test_master_failure_respects_policy(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=0, restart_policy=RestartPolicy.ON_FAILURE))
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.FAILED, 1
        )
        rec.sync(key)
        job = store.get(key)
        assert not job.is_finished()
        assert job.has_condition(ConditionType.RESTARTING)

    def test_backoff_limit_exceeded(self):
        store, runner, events, _, rec = make_harness()
        key = store.add(
            new_job(workers=1, restart_policy=RestartPolicy.ON_FAILURE, backoff_limit=2)
        )
        t = 1000.0
        for i in range(3):
            rec.sync(key, now=t)
            runner.set_all_running(key)
            self._fail_worker(runner, key, 1)
            rec.sync(key, now=t)
            t += 400.0  # past any crash-loop backoff delay
        job = store.get(key)
        assert job.is_failed()
        c = job.get_condition(ConditionType.FAILED)
        assert c.reason == "BackoffLimitExceeded"
        assert job.status.restart_count == 2

    def test_restarting_back_to_running(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1))
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        self._fail_worker(runner, key, 1)
        rec.sync(key)  # restarting
        rec.sync(key)  # recreate
        runner.set_all_running(key)
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RUNNING)
        assert not job.has_condition(ConditionType.RESTARTING)


class TestGang:
    def test_gang_blocks_partial_start(self):
        """All-or-nothing: capacity 2 < gang of 3 → nothing starts."""
        store, runner, events, _, rec = make_harness(capacity=2)
        key = store.add(new_job(workers=2))
        rec.sync(key)
        assert runner.actions == []  # no partial gang
        assert any(e.reason == "Unschedulable" for e in events.for_job(key))

    def test_gang_starts_when_capacity_allows(self):
        store, runner, _, _, rec = make_harness(capacity=3)
        key = store.add(new_job(workers=2))
        rec.sync(key)
        assert len([a for a in runner.actions if a[0] == "create"]) == 3

    def test_gang_admits_after_capacity_frees(self):
        store, runner, events, _, rec = make_harness(capacity=2)
        key = store.add(new_job(workers=2))
        rec.sync(key)
        assert runner.actions == []
        runner.capacity = 4
        rec.sync(key)
        assert len([a for a in runner.actions if a[0] == "create"]) == 3

    def test_non_gang_mode_starts_piecewise(self):
        store, runner, _, _, rec = make_harness(capacity=2, gang_enabled=False)
        key = store.add(new_job(workers=2))
        rec.sync(key)
        # non-gang: starts what fits (2 of 3)
        assert len([a for a in runner.actions if a[0] == "create"]) >= 1

    def test_group_deleted_on_finish(self):
        store, runner, _, _, rec = make_harness(capacity=3)
        key = store.add(new_job(workers=2))
        rec.sync(key)
        assert rec.gang.get_group(key) is not None
        runner.set_all_running(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.SUCCEEDED, 0
        )
        rec.sync(key)
        assert rec.gang.get_group(key) is None


class TestDeadline:
    def test_active_deadline_fails_job(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(new_job(workers=1, active_deadline_seconds=10))
        rec.sync(key, now=1000.0)
        runner.set_all_running(key)
        rec.sync(key, now=1001.0)  # sets start_time
        rec.sync(key, now=1020.0)
        job = store.get(key)
        assert job.is_failed()
        assert job.get_condition(ConditionType.FAILED).reason == "DeadlineExceeded"


class TestElastic:
    def test_worker_loss_resizes_in_place(self):
        """Partial-gang death on an elastic job shrinks the world IN
        PLACE: survivors keep running, no restart is spent, and the
        dead seat is simply retired (controller/elastic.py)."""
        store, runner, events, metrics, rec = make_harness()
        key = store.add(
            new_job(
                workers=3,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=1, max_replicas=4, max_restarts=5),
            )
        )
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        # preemption: one worker SIGKILLed
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 1), ReplicaPhase.FAILED, 137
        )
        rec.sync(key)
        job = store.get(key)
        # NOT a whole-world restart: survivors untouched, budget intact.
        assert not job.has_condition(ConditionType.RESTARTING)
        assert job.status.restart_count == 0
        assert job.status.resize_generation == 1
        live = [h.name for h in runner.list_for_job(key)]
        assert replica_name(key, ReplicaType.MASTER, 0) in live
        assert replica_name(key, ReplicaType.WORKER, 0) in live
        assert replica_name(key, ReplicaType.WORKER, 2) in live
        assert replica_name(key, ReplicaType.WORKER, 1) not in live
        assert job.spec.replica_specs[ReplicaType.WORKER].replicas == 2
        assert any(
            e.reason == "ElasticScaledDown" for e in events.for_job(key)
        )
        assert metrics.elastic_resizes.get() == 1
        # Survivor indices stay sparse: the next sync must NOT recreate
        # worker-1 (the desired indices are the live ones).
        rec.sync(key)
        assert len(runner.list_for_job(key)) == 3

    def test_hot_spare_backfills_dead_seat_without_restart(self):
        """With a warm standby ready, a partial-gang death is absorbed at
        FULL world size: the resize record keeps the dead seat in the
        member map, the create pass backfills it (the runner hands the
        create to a pre-imported standby — no cold spawn, pinned in
        test_standby), and the event says ElasticSparePromoted."""
        store, runner, events, _, rec = make_harness()
        key = store.add(
            new_job(
                workers=2,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(
                    min_replicas=1, max_replicas=3, max_restarts=5,
                    hot_spares=1,
                ),
            )
        )
        rec.sync(key)
        runner.set_all_running(key)
        runner.set_standby_target(1)
        rec.sync(key)
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 1), ReplicaPhase.FAILED, 137
        )
        rec.sync(key)
        job = store.get(key)
        assert not job.has_condition(ConditionType.RESTARTING)
        assert job.status.restart_count == 0
        assert job.status.resize_generation == 1
        # The promoted seat keeps the target world size: 2 workers.
        assert job.spec.replica_specs[ReplicaType.WORKER].replicas == 2
        assert any(
            e.reason == "ElasticSparePromoted" for e in events.for_job(key)
        )
        assert not any(
            e.reason == "ElasticScaledDown" for e in events.for_job(key)
        )
        # Next pass backfills the freed index — world back to 3 members.
        rec.sync(key)
        names = [h.name for h in runner.list_for_job(key) if h.is_active()]
        assert replica_name(key, ReplicaType.WORKER, 1) in names
        assert len(names) == 3

    def test_succeeded_worker_is_not_respawned_at_a_fresh_index(self):
        """A worker that ran to SUCCESS filled its slot forever: the
        elastic sparse-index fill must not top the count back up with a
        fresh index (a new worker joining a finishing world would die
        into a restart — the finishing-gang refill bug)."""
        store, runner, _, _, rec = make_harness()
        key = store.add(
            new_job(
                workers=1,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=1, max_replicas=2, max_restarts=4),
            )
        )
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        # Worker finishes first (the leader lingers in finalize); the
        # master is still RUNNING when the next pass looks at the gang.
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 0),
            ReplicaPhase.SUCCEEDED,
            0,
        )
        rec.sync(key)
        names = [h.name for h in runner.list_for_job(key)]
        assert replica_name(key, ReplicaType.WORKER, 1) not in names
        job = store.get(key)
        assert job.status.restart_count == 0

    def test_failover_replay_completes_resize_exactly_once(self, tmp_path):
        """Supervisor crash mid-resize: the generation bump + resize
        record committed, but the dead replica's record survived the
        crash. The NEW owner re-observes the same death, finds it ⊆ the
        record's ``handled`` set, and finishes the cleanup WITHOUT
        minting a second generation (the exactly-once contract)."""
        from pytorch_operator_tpu.controller import Reconciler as Rec

        store = JobStore()
        runner = FakeRunner()
        events_a = EventRecorder()
        rec_a = Rec(
            store=store, runner=runner, events=events_a,
            status_root=tmp_path / "status",
        )
        key = store.add(
            new_job(
                workers=2,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=1, max_replicas=3, max_restarts=5),
            )
        )
        rec_a.sync(key)
        runner.set_all_running(key)
        rec_a.sync(key)
        dead = replica_name(key, ReplicaType.WORKER, 1)
        runner.set_phase(dead, ReplicaPhase.FAILED, 137)
        rec_a.sync(key)
        assert store.get(key).status.resize_generation == 1
        # Crash aftermath: the dead record was NOT yet deleted when the
        # old owner died — the failover owner's rescan re-adopts it.
        job = store.get(key)
        runner.create(
            key, ReplicaType.WORKER, 1,
            job.spec.replica_specs[ReplicaType.WORKER].template, {},
        )
        runner.set_phase(dead, ReplicaPhase.FAILED, 137)

        events_b = EventRecorder()
        rec_b = Rec(
            store=store, runner=runner, events=events_b,
            status_root=tmp_path / "status",
        )
        rec_b.sync(key)
        job = store.get(key)
        assert job.status.resize_generation == 1  # no second bump
        assert job.status.restart_count == 0
        assert runner.get(dead) is None  # cleanup completed
        assert not any(
            e.reason in ("ElasticScaledDown", "ElasticSparePromoted")
            for e in events_b.for_job(key)
        )

    def test_master_loss_still_restarts_world(self):
        """The coordinator is the rendezvous anchor: its death cannot be
        absorbed by a resize — whole-world restart, as before."""
        store, runner, _, _, rec = make_harness()
        key = store.add(
            new_job(
                workers=2,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=1, max_replicas=4, max_restarts=5),
            )
        )
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        runner.set_phase(
            replica_name(key, ReplicaType.MASTER, 0), ReplicaPhase.FAILED, 137
        )
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RESTARTING)
        assert job.status.restart_count == 1
        assert job.status.resize_generation == 0
        # the WHOLE gang was torn down (elastic re-rendezvous)
        assert runner.list_for_job(key) == []
        # next sync recreates all 3 with bumped restart count in env
        rec.sync(key)
        assert len(runner.list_for_job(key)) == 3
        env = runner.envs[replica_name(key, ReplicaType.MASTER, 0)]
        assert env["TPUJOB_RESTART_COUNT"] == "1"

    def test_death_below_min_replicas_restarts_world(self):
        """Survivors under min_replicas cannot form a legal world — the
        classifier falls back to the whole-world restart path."""
        store, runner, _, _, rec = make_harness()
        key = store.add(
            new_job(
                workers=2,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=2, max_replicas=4, max_restarts=5),
            )
        )
        rec.sync(key)
        runner.set_all_running(key)
        rec.sync(key)
        runner.set_phase(
            replica_name(key, ReplicaType.WORKER, 0), ReplicaPhase.FAILED, 137
        )
        rec.sync(key)
        job = store.get(key)
        assert job.has_condition(ConditionType.RESTARTING)
        assert job.status.restart_count == 1
        assert job.status.resize_generation == 0
        assert "min_replicas" in job.get_condition(
            ConditionType.RESTARTING
        ).message

    def test_elastic_max_restarts_exceeded(self):
        store, runner, _, _, rec = make_harness()
        key = store.add(
            new_job(
                workers=1,
                restart_policy=RestartPolicy.EXIT_CODE,
                elastic=ElasticPolicy(min_replicas=1, max_replicas=2, max_restarts=1),
            )
        )
        for _ in range(2):
            rec.sync(key)
            runner.set_all_running(key)
            runner.set_phase(
                replica_name(key, ReplicaType.WORKER, 0), ReplicaPhase.FAILED, 137
            )
            rec.sync(key)
        job = store.get(key)
        assert job.is_failed()
        assert job.get_condition(ConditionType.FAILED).reason == "MaxRestartsExceeded"


class TestCrashLoopBackoff:
    """Kubelet CrashLoopBackOff analog: a replica dying quickly respawns
    after an exponentially growing delay instead of every sync pass
    (observed live: an argparse-rejected workload restarted ~2x/second
    under OnFailure with no backoff_limit)."""

    def _fail_master(self, store, runner, key, t):
        name = replica_name(key, ReplicaType.MASTER, 0)
        runner.set_phase(name, ReplicaPhase.FAILED, exit_code=2)
        return name

    def test_quick_failures_back_off_exponentially(self):
        store, runner, events, metrics, rec = make_harness()
        key = store.add(new_job(workers=0))
        t = 1000.0
        rec.sync(key, now=t)  # create
        spawns = 1
        # Drive many fast sync passes with instant failures: respawn
        # times must follow 1, 2, 4, 8... seconds, NOT once per pass.
        respawn_gaps = []
        last_spawn_t = t
        for _ in range(5):
            self._fail_master(store, runner, key, t)
            rec.sync(key, now=t)  # classifies + deletes + records delay
            # Poll every 0.25s until the replica respawns.
            for _ in range(10000):
                t += 0.25
                rec.sync(key, now=t)
                if runner.get(replica_name(key, ReplicaType.MASTER, 0)):
                    respawn_gaps.append(t - last_spawn_t)
                    last_spawn_t = t
                    spawns += 1
                    break
            else:
                raise AssertionError("replica never respawned")
        # Kubelet schedule: first respawn immediate (one poll tick),
        # then 1, 2, 4, 8 seconds — not once per pass.
        assert [round(g) for g in respawn_gaps] == [0, 1, 2, 4, 8], (
            respawn_gaps
        )
        assert any(
            e.reason == "CrashLoopBackOff" for e in events.for_job(key)
        )

    def test_long_uptime_resets_the_streak(self):
        from pytorch_operator_tpu.controller.reconciler import (
            CRASH_RESET_UPTIME_S,
        )

        store, runner, events, metrics, rec = make_harness()
        key = store.add(new_job(workers=0))
        t = 1000.0
        rec.sync(key, now=t)
        name = replica_name(key, ReplicaType.MASTER, 0)
        # Two quick failures build a streak...
        for _ in range(2):
            runner.set_phase(name, ReplicaPhase.FAILED, exit_code=2)
            rec.sync(key, now=t)
            t += 60.0
            rec.sync(key, now=t)
            assert runner.get(name) is not None
        # ...then a LONG healthy run that dies (preemption shape).
        h = runner.get(name)
        h.created_at = t
        runner.set_phase(name, ReplicaPhase.FAILED, exit_code=137)
        h.finished_at = t + CRASH_RESET_UPTIME_S + 1
        rec.sync(key, now=t)
        # The streak reset to 1: respawn after ~base delay, not 8s.
        t += 1.5
        rec.sync(key, now=t)
        assert runner.get(name) is not None

    def test_backoff_state_cleared_on_job_finish(self):
        store, runner, events, metrics, rec = make_harness()
        key = store.add(new_job(workers=0))
        rec.sync(key, now=1000.0)
        name = replica_name(key, ReplicaType.MASTER, 0)
        runner.set_phase(name, ReplicaPhase.FAILED, exit_code=2)
        rec.sync(key, now=1000.0)
        assert rec._crash_backoff  # recorded
        # Next life succeeds: job finishes, state pruned.
        rec.sync(key, now=1002.0)
        runner.set_phase(name, ReplicaPhase.SUCCEEDED, exit_code=0)
        rec.sync(key, now=1003.0)
        assert store.get(key).is_succeeded()
        assert not rec._crash_backoff

    def test_prune_matches_exact_replica_names_only(self):
        """'default/train' finishing must not purge sibling
        'default/train-2''s streak (the _reset_status_dir trap)."""
        store, runner, events, metrics, rec = make_harness()
        rec._crash_backoff = {
            "default/train-master-0": (3, 99.0),
            "default/train-2-master-0": (5, 99.0),
            "default/train-worker-12": (2, 99.0),
        }
        rec.prune_crash_backoff("default/train")
        assert rec._crash_backoff == {"default/train-2-master-0": (5, 99.0)}

    def test_delete_job_clears_backoff_state(self, tmp_path):
        """A deleted crash-looping job resubmitted under the same name
        must start with a clean slate (immediate first respawn)."""
        from pytorch_operator_tpu.controller.supervisor import Supervisor

        sup = Supervisor(state_dir=None, runner=FakeRunner(), persist=False)
        key = sup.submit(new_job(name="loopy", workers=0))
        sup.sync_once(now=1000.0)
        name = replica_name(key, ReplicaType.MASTER, 0)
        for t in (1000.0, 1005.0):  # two quick failures build a streak
            sup.runner.set_phase(name, ReplicaPhase.FAILED, exit_code=2)
            sup.reconciler.sync(key, now=t)
            sup.reconciler.sync(key, now=t + 4.0)
        assert sup.reconciler._crash_backoff
        sup.delete_job(key)
        assert not sup.reconciler._crash_backoff
