"""Elastic bench smoke lane (``-m bench_smoke``, also tier-1).

Runs the real resize-vs-restart harness at the smallest meaningful
scale — one 2-worker gang, both modes — and pins the elastic
tentpole's quantitative claims:

- resize-in-place recovery is STRICTLY faster than a whole-world
  restart for the same death (the whole point of shrinking instead of
  respawning);
- the post-resize rank assignment the survivors themselves report is
  unique and dense in [0, world) — no duplicate ranks, no holes;
- a shrink never cold-starts anyone (zero post-kill ``first_step``
  incarnations in the resize cell), while the restart cell respawns
  the entire gang.

The full {2,4,8}-gang artifact is ``tpujob bench-elastic``'s; this lane
keeps the 2-worker cells honest inside the tier-1 budget.
"""

from __future__ import annotations

import pytest

from pytorch_operator_tpu.workloads import elastic_bench

pytestmark = pytest.mark.bench_smoke


@pytest.fixture(scope="module")
def cells():
    out = {}
    for mode in ("resize", "restart"):
        out[mode] = elastic_bench.run_cell(
            2, mode, pre_steps=3, step_time=0.02, timeout=90.0
        )
    return out


class TestElasticBenchSmoke:
    def test_resize_strictly_faster_than_restart(self, cells):
        assert (
            cells["resize"]["recovery_s"] < cells["restart"]["recovery_s"]
        ), cells

    def test_resize_ranks_unique_and_dense(self, cells):
        assert cells["resize"]["ranks_unique_dense"] is True, cells["resize"]
        assert cells["resize"]["ranks"] == [0, 1]

    def test_shrink_never_respawns(self, cells):
        # The survivors adopt in place; nobody cold-starts.
        assert cells["resize"]["post_kill_cold_starts"] == 0, cells["resize"]

    def test_restart_respawns_the_whole_gang(self, cells):
        # Master + 2 workers all come back as fresh incarnations.
        assert cells["restart"]["post_kill_cold_starts"] == 3, cells["restart"]

    def test_neither_mode_loses_committed_steps(self, cells):
        # exit_with checkpoints every step, so both recovery paths must
        # resume at-or-past the pre-death frontier (step_loss == 0).
        for mode in ("resize", "restart"):
            assert cells[mode]["step_loss"] == 0, cells[mode]
