"""The benchmark's tests that start processes, ``exaone_moe`` part (see
``test_bench_runs.py``): the rehearsal of the cell PR 41 adds. A tiny serving
cell of the family (window + dense, window, window, full, window with experts
and a shared one, and the multi-token-prediction block that drafts;
``exaonecells.py``) runs whole on the CPU through the unedited harness and
entry, with verifying steps that yield one or two tokens a row, and is
correct; the same cell served by a program that accepts every draft is not,
nor by one that leaves a given-up position's keys in a window layer's ring.

Readings (bfloat16 activations at width 64 against the float32 reference, one
seed, limit 0.05): sound 0.014 (66% of the drafts accepted, the reference's
own block agreeing at 62% of the followed tokens); every draft accepted
0.47; a given-up position's keys left visible 0.21.
"""

from __future__ import annotations

import json

import pytest

from tests.zz_benchmark.benchproc import ROOT, run

CORE = -7  # the other whole runs keep the last six cores


def _cell(tmp_path, cell):
    rc, out = run(["-m", "tests.zz_benchmark.exaonecells", str(tmp_path / "copy"), cell, "3"], timeout=420, core=CORE)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


def test_made_up_cell_of_the_exaone_family_runs_drafting_and_is_correct(tmp_path):
    rc, out, res = _cell(tmp_path, "tiny-exaone")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap_max"]["value"] <= 0.05 and "NOT CORRECT" not in out
    # The steps really verified drafts and accepted some, and the reference's own block, followed teacher-forced
    # over the four checked requests, agrees about as often (a hundred tokens: within 15 points).
    state = ROOT / ".benchrun" / "tiny-exaone"
    final = [json.loads(l) for f in (state / "tpujob" / "status" / "default_bench").glob("*.jsonl")
             for l in f.read_text().splitlines() if '"metrics"' in l][-1]
    ref = json.loads((state / "check_out.json").read_text())
    assert final["mtp_drafts"] == final["decode_row_steps"] > 0 and 100.0 < final["decode_yield_pct"] <= 200.0
    assert 20.0 < final["mtp_accept_pct"] < 95.0 and abs(final["mtp_accept_pct"] - ref["draft_agree_pct"]) < 15.0
    assert final["cache_mtp_bytes"] > 0 and ref["draft_positions"] > 50


@pytest.mark.parametrize("broken", ["accept-all", "stale-keys"])
def test_a_program_that_accepts_every_draft_or_leaves_a_given_up_positions_keys_is_not_correct(tmp_path, broken):
    rc, out, res = _cell(tmp_path, f"tiny-exaone-{broken}")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared served_logit_gap_max = " in out and "NOT CORRECT" in out
