"""The benchmark's tests that start processes, ``jamba`` part (see
``test_bench_runs.py``): the rehearsal of the cell PR 46 adds. A tiny serving
cell of the family (six layers: attention of one key/value head under four
queries at layers 1 and 4, Mamba-1 with inner norms elsewhere;
``jambacells.py``) runs whole on the CPU through the unedited harness and
entry, and is correct, with EVERY served position of the followed requests
compared; so is the same cell under a boundary's budget of one chunk, where
every longer prompt is prefilled over several boundaries with decode
dispatches between; the same served by a program whose resumed parts start
from zero state (the row's state did not live in its slot meanwhile) is not.

Readings (bfloat16 activations at width 64 against the float32 reference,
limit 0.1): sound 0.015 whole and 0.027 split; resumed parts from zero state 2.76.
"""

from __future__ import annotations

import json

import pytest

from tests.zz_benchmark.benchproc import ROOT, run

CORE = -5


def _cell(tmp_path, cell):
    rc, out = run(["-m", "tests.zz_benchmark.jambacells", str(tmp_path / "copy"), cell, "3"], timeout=420, core=CORE)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


@pytest.mark.parametrize("cell", ["tiny-jamba", "tiny-jamba-split"])
def test_made_up_cell_of_the_jamba_family_runs_and_is_correct_whole_and_split(tmp_path, cell):
    rc, out, res = _cell(tmp_path, cell)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap_max"]["value"] <= 0.1 and "NOT CORRECT" not in out
    # nothing routes: the positions compared are all the served tokens of the four requests followed
    check_in = json.loads((ROOT / ".benchrun" / cell / "check_in.json").read_text())
    served = sum(len(r["tokens"]) for r in check_in["requests"])
    assert len(check_in["requests"]) == 4 and f"reference over 4 requests, {served} served tokens" in out


def test_a_split_prefill_whose_resumed_parts_start_from_zero_state_is_not_correct(tmp_path):
    rc, out, res = _cell(tmp_path, "tiny-jamba-lost-state")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared served_logit_gap_max = " in out and "NOT CORRECT" in out
