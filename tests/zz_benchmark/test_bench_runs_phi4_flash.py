"""The benchmark's tests that start processes, ``phi4_flash`` part (see
``test_bench_runs.py``): the rehearsal of the cell PR 36 adds. A tiny serving
cell of the family (8 layers: two Mamba-1 layers and two windows, the Mamba
layer that hands its memory on, the full layer, a gated memory unit, a cross
layer; ``phicells.py``) runs whole on the CPU through the unedited harness and
entry, and is correct, with EVERY served position of the followed requests
compared; the same cell served by a program whose prefill never writes the
slab, so that the full layer and the cross layers attend a stale row, is not;
nor is one that leaves the scan state's reset out.

Readings (bfloat16 activations at width 64 against the float32 reference,
limit 0.2): sound 0.0 to 0.01 under both traffic mixes; a stale slab row 1.16;
a state that is not reset 1.64 under prompts of 3 to 8 tokens.
"""

from __future__ import annotations

import json

import pytest

from tests.zz_benchmark.benchproc import ROOT, run

CORE = -6  # with the nemotron cells: the other whole runs keep the last five cores


def _cell(tmp_path, cell):
    rc, out = run(["-m", "tests.zz_benchmark.phicells", str(tmp_path / "copy"), cell, "3"], timeout=420, core=CORE)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


@pytest.mark.parametrize("cell", ["tiny-phi4-flash", "tiny-phi4-flash-short"])  # the two traffic mixes of the broken cells
def test_made_up_cell_of_the_phi4_flash_family_runs_and_is_correct(tmp_path, cell):
    rc, out, res = _cell(tmp_path, cell)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["compared"]["served_logit_gap_max"]["value"] <= 0.2 and "NOT CORRECT" not in out
    # nothing routes: the positions compared are all the served tokens of the four requests followed
    check_in = json.loads((ROOT / ".benchrun" / cell / "check_in.json").read_text())
    served = sum(len(r["tokens"]) for r in check_in["requests"])
    assert len(check_in["requests"]) == 4 and f"reference over 4 requests, {served} served tokens" in out


@pytest.mark.parametrize("cell", ["tiny-phi4-flash-stale-slab", "tiny-phi4-flash-stale-scan"])
def test_a_program_that_reads_a_stale_slab_row_or_keeps_a_stale_scan_state_is_not_correct(tmp_path, cell):
    rc, out, res = _cell(tmp_path, cell)
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and res["failed"] == 0
    assert "compared served_logit_gap_max = " in out and "NOT CORRECT" in out
