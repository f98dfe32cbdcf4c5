"""The benchmark's tests that start processes, ``nemotron_h`` part (see
``test_bench_runs.py``): the rehearsal of the cell PR 33 adds. A tiny serving
cell of the family (``MEM*EME``: Mamba-2 layers with their constant state,
attention without positions, experts of which a quarter are held beside a
shared one; ``nemocells.py``) runs whole on the CPU through the unedited
harness and entry, and is correct; the same cell served by a program that
leaves the state reset out is not.
"""

from __future__ import annotations

import json

from tests.zz_benchmark.benchproc import run

CORE = -6  # the other whole runs keep the last five cores


def _cell(tmp_path, cell):
    rc, out = run(["-m", "tests.zz_benchmark.nemocells", str(tmp_path / "copy"), cell, "3"], timeout=420, core=CORE)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


def test_made_up_cell_of_the_nemotron_family_runs_and_is_correct(tmp_path):
    rc, out, res = _cell(tmp_path, "tiny-nemotron")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap_max"]["value"] <= 0.2 and "NOT CORRECT" not in out


def test_a_program_that_leaves_the_state_reset_out_is_not_correct(tmp_path):
    rc, out, res = _cell(tmp_path, "tiny-nemotron-stale-state")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared served_logit_gap_max = " in out and "NOT CORRECT" in out
