"""The benchmark's tests that start processes, ``mimo_v2`` part (see
``test_bench_runs.py``): the rehearsal of the cell PR 28 adds. A tiny serving
cell of the family (layer 0 full + dense, then window and full layers with
experts of which a quarter are held; ``mimocells.py``) runs whole on the CPU
through the unedited harness and entry, and is correct; the same program
beside a reference that leaves the sink out, or selects without the bias,
is not.
"""

from __future__ import annotations

import json

import pytest

from tests.zz_benchmark.benchproc import run

CORE = -5  # the other whole runs keep the last four cores


def _cell(tmp_path, cell):
    rc, out = run(["-m", "tests.zz_benchmark.mimocells", str(tmp_path / "copy"), cell, "3"], timeout=420, core=CORE)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


def test_made_up_cell_of_the_mimo_family_runs_and_is_correct(tmp_path):
    rc, out, res = _cell(tmp_path, "tiny-mimo")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap_max"]["value"] <= 0.25 and "NOT CORRECT" not in out


@pytest.mark.parametrize("left_out", ["sink", "e-bias"])
def test_a_reference_that_leaves_a_mechanism_out_is_not_correct(tmp_path, left_out):
    rc, out, res = _cell(tmp_path, f"tiny-mimo-no-{left_out}")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared served_logit_gap_max = " in out and "NOT CORRECT" in out
