"""The serve loop's side of the measurement (workloads/serve.py): each
response carries ``ttft_ms`` in its three parts, the final record carries the
engine's counters, ``--profile-dir`` puts the loop's and the engine's spans
into a ``jax.profiler`` trace, and a hand-dropped record that states no id is
answered under its file's name."""

from __future__ import annotations

import json

import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu import profiling
from pytorch_operator_tpu.serving import Spool
from pytorch_operator_tpu.serving.engine import GAP_SEGMENTS, SEGMENTS, host_key
from pytorch_operator_tpu.workloads import serve


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    root = tmp_path_factory.mktemp("serve")
    sp = Spool(root / "spool")
    ids = [sp.submit(prompt_len=5, max_new_tokens=6), sp.submit(prompt=[1, 2, 3, 4], max_new_tokens=9),
           sp.submit(prompt_len=13, max_new_tokens=3)]
    # A foreign client's file: valid JSON, no id, and over the cache's budget.
    (root / "spool" / "requests" / "handmade.json").write_text(
        json.dumps({"prompt_len": 40, "max_new_tokens": 40, "submit_time": 0.0}))
    stats = serve.run(
        config="tiny", spool_dir=str(root / "spool"), slots=2, chunk=8, block=4, max_decode_len=48,
        max_requests=3, idle_timeout=30, profile_dir=str(root / "prof"), log=lambda *_: None,
    )
    return root, sp, ids, stats


def test_each_response_splits_its_time_to_first_token(served):
    _, sp, ids, stats = served
    assert stats["served"] == 3
    for rid in ids:
        r = sp.wait_response(rid, timeout=5)
        assert r["claim_wait_ms"] + r["slot_wait_ms"] + r["prefill_ms"] == pytest.approx(r["ttft_ms"], abs=0.01)
        assert r["claim_wait_ms"] + r["slot_wait_ms"] == pytest.approx(r["admit_wait_ms"], abs=0.01)
        assert min(r["claim_wait_ms"], r["slot_wait_ms"], r["prefill_ms"]) >= 0


def test_the_final_record_carries_the_counters_and_the_one_clock(served):
    _, _, _, stats = served
    assert stats["admitted"] == 3 and stats["decode_blocks"] >= 2 and stats["prefill_tokens"] == 5 + 4 + 13
    assert 0 < stats["slot_occupancy_pct"] <= 100 and 0 < stats["decode_yield_pct"] <= 100
    assert stats["host_gap_s"] == pytest.approx(sum(stats[host_key(k)] for k in GAP_SEGMENTS))
    assert all(stats[host_key(k)] >= 0 for k in SEGMENTS)
    assert stats["host_gap_poll_s"] > 0 and stats["host_gap_respond_s"] > 0 and stats["host_gap_submit_s"] > 0


def test_a_record_without_an_id_is_answered_under_its_files_name(served):
    root, sp, _, stats = served
    assert stats["rejected"] == 1
    answer = sp.wait_response("handmade", timeout=5)
    assert answer["id"] == "handmade" and "budget" in answer["error"]
    assert not (root / "spool" / "responses" / "unknown.json").exists()
    assert not (root / "spool" / "claimed" / "handmade.json").exists()  # the claim is released, not recycled


def test_profile_dir_holds_the_loops_and_the_engines_spans(served):
    root, _, _, _ = served
    report = profiling.device_report(root / "prof", device_substr="host:CPU")
    ops = {r["op"] for r in report["top_ops"]}
    assert {"serve.poll", "serve.submit", "serve.respond", "engine.step", "engine.admit", "engine.decode_fence"} <= ops
