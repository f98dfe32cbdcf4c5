"""The benchmark's tests that start processes, second-family part (see
``test_bench_runs.py``): the rehearsal of a PR that brings a model of
another shape. A made-up training cell whose block has a layer of experts
(the trainer's ``MoEMLP``: four experts, two a token, stated once, by the family's preset) comes with its family
(``data/families/tiny-moe``: reference, weights, install, flops) as files and
entries only; a whole run of it on the CPU is correct, and the same program
beside a reference whose router keeps one expert a token (the
configuration's ``bench.reference_keeps``) is not.
"""

from __future__ import annotations

from tests.zz_benchmark.benchproc import tiny_cell

CORE = -4  # the other whole runs and the controls keep the last three cores


def test_made_up_cell_of_a_second_family_runs_and_is_correct(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-moe", core=CORE)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 3
    assert set(res["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}
    assert "compared grad_norm_gap_worst_leaf = " in out and "NOT CORRECT" not in out


def test_a_reference_whose_router_keeps_one_expert_is_not_correct(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-top1", core=CORE)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared grad_norm_gap_worst_leaf = " in out and "NOT CORRECT" in out
