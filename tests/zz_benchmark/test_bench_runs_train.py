"""The benchmark's tests that start processes, training part (see
``test_bench_runs.py``): a whole run of a made-up tiny training cell on the
CPU, correct; the same with a step that returns its state unchanged, not
correct.
"""

from __future__ import annotations

from tests.zz_benchmark.benchproc import tiny_cell

CORE = -2  # the serving half keeps the last core


def test_made_up_training_cell_runs_and_is_correct(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-pre", core=CORE)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    for number in ("loss_gap_step1", "loss_gap_step3", "grad_norm_gap_worst_leaf", "delta_norm_gap_worst_leaf"):
        assert f"compared {number} = " in out


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-pre-stuck", module="tests.zz_benchmark.broken_train", core=CORE)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False
    assert "compared delta_norm_gap_worst_leaf = 1.0 " in out and "NOT CORRECT" in out
