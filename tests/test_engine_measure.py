"""The measurement inside the serving engine (serving/engine.py) and the
span API it uses (obs/trace.py).

- counters: on the ``tiny`` config a fixed request list gives the same
  counts run to run; ``reset_stats()`` clears them; per request
  ``claim_wait + slot_wait + prefill == ttft``; every second of the serving
  thread is charged to one segment and the gap is the sum of its segments;
- spans: ``engine.step`` names its children's parent (file records), and a
  ``jax.profiler`` trace around two engine steps holds ``engine.admit`` with
  its ``rid`` (the annotation mirror), with ``TPUJOB_TRACE_DIR`` unset.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.obs import trace as obs_trace
from pytorch_operator_tpu.serving import Request, ServingEngine
from pytorch_operator_tpu.serving import engine as engine_lib
from pytorch_operator_tpu.serving.engine import FENCE_SEGMENTS, GAP_SEGMENTS, SEGMENTS, SIZED_BY, host_key

SHAPES = [(5, 7), (13, 9), (8, 1), (21, 5), (3, 12)]  # (prompt, new tokens); one finishes inside prefill
COUNTERS = ("decode_blocks", "decode_steps", "slot_blocks_occupied", "decode_row_steps", "decode_tokens",
            "prefill_chunks",
            "prefill_tokens", "prefill_pad_tokens", "admit_rounds", "decode_behind_admit", "admitted")


@pytest.fixture(scope="module")
def model():
    import flax.linen as nn
    import jax

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=48)
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32)
        )["params"]
    )
    return cfg, params


def _engine(model):
    return ServingEngine(*model, slots=3, chunk=8, block=4)


def _submit_all(eng, shapes=SHAPES, prefix="r"):
    rng = np.random.default_rng(0)
    for i, (p, n) in enumerate(shapes):
        eng.submit(Request(id=f"{prefix}{i}", prompt=rng.integers(0, 256, (p,)).astype(np.int32),
                           max_new_tokens=n, submit_time=time.time() - 0.01 * i))


def _counts(stats):
    return {k: stats[k] for k in COUNTERS}


def test_counters_repeat_exactly_and_say_what_they_count(model, monkeypatch):
    rule, sized = engine_lib.decode_steps, []  # what the rule returned for each dispatch: (steps, reason)
    monkeypatch.setattr(engine_lib, "decode_steps", lambda *a: sized.append(rule(*a)) or sized[-1])
    runs = []
    for _ in range(2):
        eng = _engine(model)
        _submit_all(eng)
        results, row_steps, before = [], 0, _counts(eng.stats())
        while eng.busy:
            results += eng.step()
            now = _counts(eng.stats())
            assert now["decode_blocks"] - before["decode_blocks"] <= 1  # a step is one dispatch at most
            assert now["decode_steps"] - before["decode_steps"] == eng.last_steps * (
                now["decode_blocks"] - before["decode_blocks"])
            row_steps += (now["slot_blocks_occupied"] - before["slot_blocks_occupied"]) * (
                now["decode_steps"] - before["decode_steps"])
            before = now
        runs.append((before, {r.id: r.tokens for r in results}, row_steps))
    assert runs[0] == runs[1]
    n, _, row_steps = runs[0]
    assert n["admitted"] == len(SHAPES)
    assert n["prefill_tokens"] == sum(p for p, _ in SHAPES)
    assert n["prefill_chunks"] == sum(-(-p // 8) for p, _ in SHAPES)
    assert n["prefill_pad_tokens"] == n["prefill_chunks"] * 8 - n["prefill_tokens"]
    # The first token of each request comes out of prefill; the blocks yield the rest.
    assert n["decode_tokens"] == sum(new - 1 for _, new in SHAPES)
    # Row-steps are the sum over dispatches of rows x the steps that dispatch ran (not rows x block).
    assert n["decode_row_steps"] == row_steps <= 4 * n["slot_blocks_occupied"]
    assert n["decode_blocks"] <= n["decode_steps"] <= 4 * n["decode_blocks"]
    assert len(sized) == 2 * n["decode_blocks"] and sized[: n["decode_blocks"]] == sized[n["decode_blocks"]:]
    assert sum(steps for steps, _ in sized) == 2 * n["decode_steps"]
    assert {"ceiling", "budget"} <= {reason for _, reason in sized} <= set(SIZED_BY)  # block 4 cuts; so do last tokens
    assert n["decode_blocks"] <= n["slot_blocks_occupied"] <= 3 * n["decode_blocks"]
    assert 1 <= n["admit_rounds"] <= n["admitted"]
    # Every round here admits a row that decodes, so each one's decode dispatch was queued behind it, unread.
    assert n["decode_behind_admit"] == n["admit_rounds"] < n["decode_blocks"]
    stats = eng.stats()
    assert stats["slot_occupancy_pct"] == pytest.approx(
        100 * n["slot_blocks_occupied"] / (3 * n["decode_blocks"]), abs=1e-3)
    assert stats["decode_yield_pct"] == pytest.approx(100 * n["decode_tokens"] / n["decode_row_steps"], abs=1e-3)
    assert 0 < stats["decode_yield_pct"] < 100  # rows finish inside a dispatch: some steps yield nothing
    assert stats["decode_steps_per_block"] == pytest.approx(n["decode_steps"] / n["decode_blocks"], abs=1e-3)
    assert stats["prefill_pad_pct"] == pytest.approx(
        100 * n["prefill_pad_tokens"] / (n["prefill_chunks"] * 8), abs=1e-3) and 0 < stats["prefill_pad_pct"] < 100


def test_reset_clears_the_counters_and_the_clock(model):
    eng = _engine(model)
    _submit_all(eng)
    eng.run_until_drained()
    assert eng.stats()["decode_blocks"] > 0 and eng.stats()["host_gap_s"] > 0
    eng.reset_stats()
    stats = eng.stats()
    assert all(stats[k] == 0 for k in COUNTERS)
    assert all(stats[host_key(k)] == 0.0 for k in SEGMENTS) and stats["host_gap_s"] == 0.0
    assert stats["slot_occupancy_pct"] is None and stats["decode_yield_pct"] is None
    assert stats["prefill_pad_pct"] is None and stats["decode_steps_per_block"] is None
    assert stats["requests"] == 0


def test_ttft_is_the_sum_of_its_three_parts(model):
    eng = _engine(model)
    _submit_all(eng)
    results = eng.run_until_drained()
    assert len(results) == len(SHAPES)
    for r in results:
        assert r.claim_wait_s + r.slot_wait_s + r.prefill_s == pytest.approx(r.ttft_s, abs=1e-9)
        assert r.claim_wait_s + r.slot_wait_s == pytest.approx(r.admit_wait_s, abs=1e-9)
        assert r.claim_wait_s >= 0 and r.slot_wait_s >= 0 and r.prefill_s > 0
    # With three slots the fourth request waits for one; the first does not wait a block.
    by_id = {r.id: r for r in results}
    assert by_id["r3"].slot_wait_s > by_id["r0"].slot_wait_s


def test_every_second_of_the_serving_thread_goes_to_one_segment(model):
    eng = _engine(model)
    eng.reset_stats()
    t0 = time.perf_counter()
    _submit_all(eng)
    eng.host_lap("submit")
    while eng.busy:
        eng.step()
        eng.host_lap("respond")
    time.sleep(0.02)
    eng.host_lap("idle")
    wall = time.perf_counter() - t0
    stats = eng.stats()
    segments = {k: stats[host_key(k)] for k in SEGMENTS}
    assert all(v >= 0 for v in segments.values())
    assert sum(segments.values()) == pytest.approx(wall, abs=5e-3)
    assert stats["host_gap_s"] == pytest.approx(sum(segments[k] for k in GAP_SEGMENTS), abs=1e-12)
    # The gap's parts are found by their key (the benchmark's reader keeps no list of them).
    assert {k for k in stats if k.startswith("host_gap_") and k != "host_gap_s"} == {
        f"host_gap_{k}_s" for k in GAP_SEGMENTS}
    assert host_key("accept") == "host_gap_accept_s" and host_key("idle") == "host_idle_s"
    assert segments["idle"] >= 0.02 and segments["poll"] == 0.0
    assert all(segments[k] > 0 for k in FENCE_SEGMENTS + (
        "admit_prep", "accept", "harvest", "dispatch", "overlapped", "submit"))
    # What runs while a dispatch is in flight is no part of the gap.
    assert "overlapped" not in GAP_SEGMENTS and host_key("overlapped") == "host_overlapped_s"


@pytest.fixture
def traced_dir(tmp_path, monkeypatch):
    d = tmp_path / "trace"
    monkeypatch.setenv(obs_trace.ENV_VAR, str(d))
    obs_trace.reset_tracer()
    yield d
    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()


def test_engine_spans_nest_under_the_step_and_requests_keep_their_hops(model, traced_dir):
    eng = _engine(model)
    _submit_all(eng)
    eng.run_until_drained()
    rec = obs_trace.tracer()
    rec.flush()
    spans = [e for e in obs_trace.load_span_file(rec.path) if e["ph"] == "X"]
    by_id = {e["id"]: e for e in spans}
    names = {e["name"] for e in spans}
    assert {"engine.step", "engine.admit", "engine.prefill_dispatch", "engine.first_token",
            "engine.decode_dispatch", "engine.decode_fence", "engine.accept", "engine.harvest"} <= names
    for e in spans:
        if e["name"] in ("engine.admit", "engine.decode_dispatch", "engine.first_token", "engine.decode_fence",
                         "engine.accept", "engine.harvest"):
            assert by_id[e["parent"]]["name"] == "engine.step"
        if e["name"] == "engine.prefill_dispatch":
            assert by_id[e["parent"]]["name"] == "engine.admit"
    # A step's order (PR 35): every admission dispatched, then the decode dispatch, and only then the fences:
    # on the admissions' first tokens, in admission order, before the one on the decode tokens.
    firsts = 0
    for step in (e for e in spans if e["name"] == "engine.step"):
        inside = sorted((e for e in spans if e.get("parent") == step["id"]), key=lambda e: e["ts"])
        order = [e["name"] for e in inside if e["name"] != "engine.harvest"]
        admits = order.count("engine.admit")
        assert order == ["engine.admit"] * admits + ["engine.decode_dispatch"] + ["engine.first_token"] * bool(admits) + [
            "engine.decode_fence", "engine.accept"], order
        ends = [e["ts"] + e["dur"] for e in inside if e["name"] in ("engine.admit", "engine.decode_dispatch")]
        assert all(e["ts"] >= max(ends) for e in inside if e["name"] == "engine.first_token")
        # ONE fence span a boundary, which says how many first tokens it took.
        firsts += sum(e["args"]["n"] for e in inside if e["name"] == "engine.first_token")
        assert sum(e["args"]["n"] for e in inside if e["name"] == "engine.first_token") == admits
    assert firsts == len(SHAPES)
    admits = [e for e in spans if e["name"] == "engine.admit"]
    assert sorted(e["args"]["rid"] for e in admits) == [f"r{i}" for i in range(len(SHAPES))]
    assert all(e["args"]["chunks"] == -(-e["args"]["prompt_len"] // 8) for e in admits)
    # The request hops `tpujob why` reads, from the engine's own timestamps.
    for hop in ("slot_wait", "decode"):
        got = [e for e in spans if e["name"] == hop and e["cat"] == "serve"]
        assert sorted(e["args"]["rid"] for e in got) == [f"r{i}" for i in range(len(SHAPES))]
    # A step's self time is its span less its children.
    step = next(e for e in spans if e["name"] == "engine.step")
    children = [e for e in spans if e.get("parent") == step["id"]]
    assert children and sum(c["dur"] for c in children) <= step["dur"] + 1.0


def test_a_profiler_trace_holds_the_engines_spans_with_their_arguments(model, tmp_path, monkeypatch):
    import jax

    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()
    assert obs_trace.tracer() is None
    eng = _engine(model)
    _submit_all(eng, SHAPES[:1], prefix="w")  # compile outside the trace
    eng.run_until_drained()
    before = obs_trace.records_emitted()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _submit_all(eng, SHAPES[:3])
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    assert obs_trace.records_emitted() == before  # the mirror writes no file record
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    host = [ev for plane in data.planes if plane.name == "/host:CPU" for line in plane.lines for ev in line.events]
    admits = [dict(ev.stats) for ev in host if ev.name == "engine.admit"]
    assert sorted(stats["rid"] for stats in admits) == ["r0", "r1", "r2"]
    assert {ev.name for ev in host} >= {"engine.step", "engine.decode_fence", "engine.prefill_dispatch"}
    assert sum(ev.name == "engine.step" for ev in host) == 2
