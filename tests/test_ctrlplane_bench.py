"""Control-plane bench smoke lane (``-m bench_smoke``, also tier-1).

Runs the real harness at N=10 with few passes — small enough for the
tier-1 time budget, real enough to catch hot-path regressions: a change
that reintroduces per-pass re-reads, per-pass rewrites of idle jobs, or
per-job directory globs shows up here as nonzero idle I/O, long before
anyone reruns the full N=1000 artifact.
"""

from __future__ import annotations

import json
import math

import pytest

from pytorch_operator_tpu.controller.autoscale import PoolAutoscaler
from pytorch_operator_tpu.workloads import ctrlplane_bench

pytestmark = pytest.mark.bench_smoke


@pytest.fixture(scope="module")
def smoke_result(tmp_path_factory):
    td = tmp_path_factory.mktemp("ctrlplane")
    return ctrlplane_bench.run(
        jobs=[10], passes=5, work_dir=str(td), log=lambda *_: None
    )


def cell(result, mode):
    return next(c for c in result["cells"] if c["mode"] == mode)


@pytest.fixture(scope="module")
def sharded_result(tmp_path_factory):
    """A real two-supervisor sharded cell at smoke scale: one shared
    state dir, per-shard leases, each supervisor running the full
    daemon loop body."""
    td = tmp_path_factory.mktemp("ctrlplane-sharded")
    return ctrlplane_bench.bench_sharded(
        40, 2, 6, td, lease_ttl=1.0, log=lambda *_: None
    )


@pytest.fixture(scope="module")
def churn_result(tmp_path_factory):
    td = tmp_path_factory.mktemp("ctrlplane-churn")
    return ctrlplane_bench.bench_sharded(
        24, 2, 4, td, replicas=3, churn_markers=8, lease_ttl=1.0,
        log=lambda *_: None,
    )


class TestShardedSmoke:
    def test_no_job_is_double_reconciled(self, sharded_result):
        # THE exactly-once pin: under a 2-supervisor split, no job ever
        # has live worlds in both runners.
        assert sharded_result["double_reconciles"] == 0

    def test_every_job_has_exactly_one_owner(self, sharded_result):
        assert sum(sharded_result["jobs_per_supervisor"]) == 40
        assert all(n > 0 for n in sharded_result["jobs_per_supervisor"])

    def test_idle_store_io_is_zero_per_shard_owner(self, sharded_result):
        # The zero-idle-I/O invariant survives the shard split: each
        # supervisor's idle pass reads/writes NO job files for its
        # shards (lease renewals live outside the store on purpose).
        assert sharded_result["idle_reads_per_pass_per_supervisor"] == [0, 0]
        assert sharded_result["idle_writes_per_pass_per_supervisor"] == [0, 0]

    def test_autoscaler_respects_its_bounds(self, sharded_result):
        # Pool never exceeds --sync-workers-max ...
        floor = sharded_result["sync_pool_floor"]
        ceiling = sharded_result["sync_pool_ceiling"]
        final = sharded_result["sync_pool_final"]
        assert sharded_result["sync_pool_max_seen"] <= ceiling
        assert floor <= final <= ceiling
        # ... and an idle fleet shrinks it back to the floor. Where the
        # bench's last pass leaves the pool is the box's (a drain pass
        # that ran slow grows it for the next one), so the shrink is
        # driven from there: the passes of a drained fleet report no
        # steady work, and the control law halves the pool once a
        # `shrink_patience` of them.
        scaler = PoolAutoscaler(floor, ceiling)
        scaler.size = final
        halvings = max(1, math.ceil(math.log2(ceiling)))
        for _ in range(scaler.shrink_patience * halvings):
            scaler.observe(0.0, 0)
        assert scaler.size == floor

    def test_drain_completes_across_supervisors(self, sharded_result):
        assert sharded_result["unfinished_after_drain"] == 0

    def test_shard_split_is_disjoint_and_complete(self, sharded_result):
        split = sharded_result["shard_split"]
        all_shards = [s for owned in split.values() for s in owned]
        assert sorted(all_shards) == list(range(sharded_result["shards"]))

    def test_churn_cell_stays_exactly_once_with_wide_gangs(self, churn_result):
        # Marker storms (rename-claimed across two supervisors) on
        # 3-replica gangs: still no double worlds, still drains clean.
        assert churn_result["double_reconciles"] == 0
        assert churn_result["unfinished_after_drain"] == 0
        assert churn_result["churn_passes"] > 0
        assert churn_result["churn_pass_ms_p50"] > 0


class TestBenchSmoke:
    def test_cached_idle_pass_does_zero_job_file_io(self, smoke_result):
        cached = cell(smoke_result, "cached")
        # THE hot-path guard: an idle pass over a cached store must not
        # read or write a single job file. Any regression that puts
        # file I/O back on the steady-state path trips this.
        assert cached["idle_reads_per_pass"] == 0
        assert cached["idle_writes_per_pass"] == 0
        # O(1) clean check (TPUJob generation counter): the idle pass
        # does not even SERIALIZE a job to discover it is clean.
        assert cached["idle_serializations_per_pass"] == 0
        # One scandir snapshot serves rescan + all marker scans.
        assert cached["idle_scans_per_pass"] <= 1.0

    def test_watch_engine_is_free_on_idle_fleets(self, smoke_result):
        # The live health engine (obs/watch.py) rides the same pass:
        # jobs that never reported must cost it NOTHING — no alert-log
        # appends, and not even a rule evaluation (untracked jobs skip
        # the evaluator entirely). Both modes, since the watch runs
        # regardless of the store flavor.
        for mode in ("cached", "legacy"):
            c = cell(smoke_result, mode)
            assert c["idle_watch_log_appends"] == 0
            assert c["idle_watch_evaluations"] == 0

    def test_remediation_engine_is_free_on_healthy_fleets(self, smoke_result):
        # Every bench job carries an ARMED remediation policy, nothing
        # ever fires: across the idle passes the engine must write no
        # audit records and take no actions — the closed loop costs
        # zero I/O until an alert actually asks for an action.
        for mode in ("cached", "legacy"):
            c = cell(smoke_result, mode)
            assert c["idle_remediation_log_appends"] == 0
            assert c["idle_remediation_actions"] == 0

    def test_legacy_mode_still_measures_the_old_profile(self, smoke_result):
        legacy = cell(smoke_result, "legacy")
        # The baseline must stay honest: N reads and N writes per idle
        # pass (one per job), plus the per-kind marker globs — otherwise
        # the artifact's comparison silently measures nothing.
        assert legacy["idle_reads_per_pass"] == 10
        assert legacy["idle_writes_per_pass"] == 10
        assert legacy["idle_scans_per_pass"] >= 5

    def test_churn_completes_all_jobs_in_both_modes(self, smoke_result):
        for mode in ("cached", "legacy"):
            assert cell(smoke_result, mode)["unfinished_after_drain"] == 0

    def test_artifact_shape_is_committed_schema(self, smoke_result, tmp_path):
        out = tmp_path / "bench.json"
        ctrlplane_bench.run(
            jobs=[10], passes=2, out=str(out),
            work_dir=str(tmp_path), log=lambda *_: None,
        )
        data = json.loads(out.read_text())
        assert data["bench"] == "control_plane"
        assert data["comparisons"][0]["jobs"] == 10
        for field in (
            "pass_p50_speedup",
            "pass_p99_speedup",
            "idle_read_reduction",
            "idle_write_reduction",
        ):
            assert field in data["comparisons"][0]
