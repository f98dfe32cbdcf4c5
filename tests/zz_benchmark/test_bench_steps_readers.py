"""The two per-layer readers PR 25 adds (``decode_steps_per_block.ttft`` and
``.serve_tps``): on the final record a tiny engine really produces, on the
recorded contexts of PR 24's chip runs (a program without the counter: the
parent commit), and as BENCHMARK.json declares them. A file of its own beside
``test_bench_span_readers.py``, which this PR may not edit."""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL_OF = {"decode_steps_per_block.ttft": "serve-internlm2-chat",
           "decode_steps_per_block.serve_tps": "serve-internlm2-longprompt"}


@pytest.mark.parametrize("name", sorted(CELL_OF))
def test_declared_beside_the_yield_of_the_same_cell(name):
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    suffix = name.rsplit(".", 1)[-1]
    (beside,) = [m for m in MANIFEST["per_layer"] if m["name"] == {
        "ttft": "claim_wait_mean_ms", "serve_tps": "decode_yield_pct.serve_tps"}[suffix]]
    # The cell PR 25 gave it, among those later PRs appended (PR 28 on), and the yield's too.
    assert CELL_OF[name] in entry["workloads"] and CELL_OF[name] in beside["workloads"]
    assert (entry["moves"], entry["layer"], entry["source"]) == (beside["moves"], beside["layer"], "program_counter")
    # Appended after PR 24's; what later PRs appended follows, and is theirs to pin.
    assert MANIFEST["per_layer"].index(entry) > MANIFEST["per_layer"].index(beside)


@pytest.mark.parametrize("name", sorted(CELL_OF))
def test_the_parents_record_has_nothing_to_read(name):
    ctx = json.loads((DATA / f"ctx.{CELL_OF[name]}.json").read_text())  # PR 24's program: no such counter
    assert "decode_blocks" in ctx["final"] and "decode_steps_per_block" not in ctx["final"]
    assert run.read_layer_metric(name, ctx) is None
    assert run.read_layer_metric(name, {"cell": {"name": "a-cell"}, "final": {}}) is None
    assert run.read_layer_metric(name, {"cell": {"name": "a-cell"}, "final": {"decode_steps_per_block": None}}) is None


@pytest.fixture(scope="module")
def final_record():
    """The final ``metrics`` record of a tiny engine's run, as the status file carries it."""
    from tests.test_serving_engine import _cfg_params, _req  # the engine tests' tiny model (imports JAX)

    from pytorch_operator_tpu.serving import ServingEngine

    eng = ServingEngine(*_cfg_params(), slots=2, chunk=8, block=32)
    rng = np.random.default_rng(0)
    for i, (p, n) in enumerate([(5, 20), (9, 7), (4, 11)]):
        eng.submit(_req(f"r{i}", rng.integers(0, 256, (p,)).astype(np.int32), n))
    eng.run_until_drained()
    return json.loads(json.dumps(eng.stats()))


@pytest.mark.parametrize("name", sorted(CELL_OF))
def test_reads_the_engines_own_final_record(name, final_record):
    got = run.read_layer_metric(name, {"cell": {"name": "a-cell"}, "final": final_record})
    assert got == pytest.approx(final_record["decode_steps"] / final_record["decode_blocks"], abs=1e-3)
    assert 1 <= got <= 32 and final_record["decode_row_steps"] <= 2 * final_record["decode_steps"]


def test_generator_lateness_is_the_mean_of_the_open_loops_own_list(capsys):
    """``generator_late_ms.ttft`` (PR 26): sent - due of each request, as ``drive_serve`` keeps it."""
    late = [0.001, 0.002, 0.0015, 0.0305]
    assert run.read_layer_metric("generator_late_ms.ttft", {"load": {"lateness": late}}) == pytest.approx(8.75)
    assert capsys.readouterr().out == ""  # the maximum is on run.py's own line, once
    assert run.read_layer_metric("generator_late_ms.ttft", {"load": {"lateness": []}}) is None  # a closed loop has no due time
    assert run.read_layer_metric("generator_late_ms.ttft", {}) is None


@pytest.mark.parametrize("name", ["generator_stall_ms_per_s.ttft", "generator_stall_ms_per_s.serve_tps"])
def test_generator_stalls_are_the_overruns_per_second_of_window(name):
    """PR 26: a sleep of the generator that overran is a pause of the whole machine."""
    ctx = {"seconds": 50.0, "load": {"stalls": [0.115, 0.735, 0.12]}}
    assert run.read_layer_metric(name, ctx) == pytest.approx(19.4)
    assert run.read_layer_metric(name, {"seconds": 50.0, "load": {"stalls": []}}) == 0.0  # a window without a pause
    assert run.read_layer_metric(name, {"seconds": 50.0}) is None  # a training cell has no generator
