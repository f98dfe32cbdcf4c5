"""The trace reduction on one small recorded trace (a v5e chip, three calls
each of two named programs) and on made-up intervals: busy union, idle
share, program shares clipped to the window — no share can pass 100."""

from __future__ import annotations

from pathlib import Path

import pytest

from benchmark import trace_reduce as R

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


def test_interval_arithmetic():
    assert R.merge([(5, 9), (1, 3), (2, 4), (9, 9)]) == [[1, 4], [5, 9]]
    assert R.length(R.merge([(0, 10), (5, 12), (20, 21)])) == 13
    assert R.clip([[0, 10], [20, 30]], 5, 25) == [[5, 10], [20, 25]]
    assert R.intersect([[0, 10], [20, 30]], [[5, 22], [29, 40]]) == [[5, 10], [20, 22], [29, 30]]
    assert R.gaps([[2, 4], [6, 7]], 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_shares_cannot_pass_100_with_overlaps_and_events_past_the_window():
    planes = {
        "/device:TPU:0": {
            # Nested and overlapping operations: a while loop and its body.
            "XLA Ops": [("%while.1 = ...", 100, 200), ("%fusion.1 = ...", 100, 150), ("%fusion.2 = ...", 150, 200),
                        ("%all-reduce.3 = ...", 300, 340), ("%copy.1 = ...", 390, 400)],
            # A program's span is longer than its operations and laps past the last operation.
            "XLA Modules": [("jit_prefill_chunk(1)", 90, 210), ("jit_decode_block(2)", 295, 460)],
        },
        "/host:CPU": {"python": [("PjitFunction(prefill_chunk)", 200, 300), ("whole_run", 0, 1000)]},
    }
    out = R.reduce_planes(planes, ("prefill_chunk", "decode_block"))
    assert out["window_s"] == pytest.approx(300e-9) and out["busy_s"] == pytest.approx(150e-9)
    shares = {p: 100 * s / out["busy_s"] for p, s in out["program_s"].items()}
    assert shares["prefill_chunk"] == pytest.approx(100 * 100 / 150)
    assert shares["decode_block"] == pytest.approx(100 * 50 / 150)
    assert sum(shares.values()) <= 100.0 + 1e-9
    assert out["collective_s"] == pytest.approx(40e-9)
    assert "while.1" not in dict(out["device_ops"])  # the container is not listed beside its body
    assert out["idle_gaps"][0] == ["PjitFunction_prefill_chunk_", pytest.approx(100e-9)]
    assert 0 < 1 - out["busy_s"] / out["window_s"] < 1


def test_recorded_trace_from_the_chip():
    out = R.reduce_planes(R.read_planes(str(TRACE)), ("prefill_chunk", "decode_block"))
    assert 0 < out["busy_s"] < out["window_s"] < 1.0
    idle_pct = 100 * (1 - out["busy_s"] / out["window_s"])
    assert 99 < idle_pct < 100  # three tiny calls with sleeps between them
    total = sum(out["program_s"].values())
    assert total == pytest.approx(out["busy_s"], rel=1e-3)  # every operation ran inside one of the two programs
    assert all(0 < 100 * s / out["busy_s"] < 100 for s in out["program_s"].values())
    assert len(out["program_run_s"]["prefill_chunk"]) == 3 and len(out["program_run_s"]["decode_block"]) == 3
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][0] == "time_sleep"
    assert R.reduce_planes({}, ()) == {} and R.reduce_dir("/nonexistent") == {}
