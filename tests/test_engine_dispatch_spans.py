"""The dispatch is the unit of the trace (serving/engine.py,
workloads/serve.py): what a dispatch's span says equals what the counters
added for it, so a reader of the spans of ANY stretch has that stretch's
counts; the serve loop's side of a boundary is one span, ``serve.boundary``,
beside ``engine.step`` and around the loop's own phases; and with tracing off
none of it writes a record."""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu import obs
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.obs import trace as obs_trace
from pytorch_operator_tpu.serving import Request, ServingEngine, Spool
from pytorch_operator_tpu.serving.engine import SIZED_BY
from pytorch_operator_tpu.workloads import serve

CHUNK = 8
# (prompt, new tokens). "whole": every budget after the first token is a multiple of a quantum, so every row
# takes every step of every dispatch; "ragged": rows end inside a dispatch, and one inside its prefill.
SHAPES = {"whole": [(5, 9), (13, 17), (8, 9), (21, 25), (3, 17)],
          "ragged": [(5, 7), (13, 9), (8, 1), (21, 5), (3, 12)]}


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


@pytest.fixture
def traced_dir(tmp_path, monkeypatch):
    monkeypatch.setenv(obs_trace.ENV_VAR, str(tmp_path / "trace"))
    obs_trace.reset_tracer()
    yield tmp_path / "trace"
    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()


def _spans() -> list:
    rec = obs_trace.tracer()
    rec.flush()
    return [e for e in obs_trace.load_span_file(rec.path) if e["ph"] == "X"]


def _args(spans, name) -> list:
    return [e["args"] for e in spans if e["name"] == name]


@pytest.mark.parametrize("shapes", sorted(SHAPES))
@pytest.mark.parametrize("slots", [2, 6], ids=["queued", "free"])
def test_the_spans_of_the_dispatches_sum_to_the_counters(model, traced_dir, shapes, slots):
    eng = ServingEngine(*model, slots=slots, chunk=CHUNK, block=32)
    rng = np.random.default_rng(0)
    for i, (p, n) in enumerate(SHAPES[shapes]):
        eng.submit(Request(id=f"r{i}", prompt=rng.integers(0, 256, (p,)).astype(np.int32),
                           max_new_tokens=n, submit_time=time.time()))
    eng.run_until_drained()
    n, spans = eng.stats(), _spans()
    fences, dispatches = _args(spans, "engine.decode_fence"), _args(spans, "engine.decode_dispatch")
    assert len(fences) == len(dispatches) == n["decode_blocks"] > 0
    # The fence repeats its dispatch's rows and steps (a reader needs only one of the two spans).
    assert [(f["rows"], f["steps"]) for f in fences] == [(d["rows"], d["steps"]) for d in dispatches]
    assert all(d["sized_by"] in SIZED_BY for d in dispatches)
    assert sum(f["steps"] for f in fences) == n["decode_steps"]
    assert sum(f["rows"] for f in fences) == n["slot_blocks_occupied"]
    assert sum(f["rows"] * f["steps"] for f in fences) == n["decode_row_steps"]
    assert sum(f["attended"] for f in fences) == n["decode_attended_positions"]
    # ``live`` is taken at the dispatch's first step; a row that takes every step adds one position a step.
    live = sum(f["steps"] * f["live"] + f["rows"] * f["steps"] * (f["steps"] - 1) // 2 for f in fences)
    if shapes == "whole":
        assert n["decode_yield_pct"] == 100.0 and live == n["decode_live_positions"]
    else:
        assert n["decode_yield_pct"] < 100.0 and live > n["decode_live_positions"]
    assert all(f["attended"] >= f["live"] for f in fences)  # whole blocks, no fewer than the positions live
    # The chunks: their prompt tokens, their pads, and the one of each prompt behind which the head ran.
    chunks = _args(spans, "engine.prefill_dispatch")
    assert len(chunks) == n["prefill_chunks"]
    assert sum(c["n_real"] for c in chunks) == n["prefill_tokens"]
    # Each says how wide it was (PR 47: a model that takes a wide chunk gets two widths; this slab of 48 has one).
    assert {c["width"] for c in chunks} == {CHUNK} and n["prefill_wide_chunks"] == 0
    assert sum(c["width"] - c["n_real"] for c in chunks) == n["prefill_pad_tokens"]
    assert sum(c["head"] for c in chunks) == n["prefill_head_chunks"] == n["admitted"]
    by_admit = {}
    for e in spans:
        if e["name"] == "engine.prefill_dispatch":
            by_admit.setdefault(e["parent"], []).append(e["args"])
    for e in (e for e in spans if e["name"] == "engine.admit"):
        mine = by_admit[e["id"]]
        assert [c["slot"] for c in mine] == [e["args"]["slot"]] * e["args"]["chunks"]
        assert sum(c["n_real"] for c in mine) == e["args"]["prompt_len"]
        assert [c["head"] for c in mine] == [False] * (len(mine) - 1) + [True]
    # One fence a boundary on the first tokens, which says how many it took.
    assert sum(f["n"] for f in _args(spans, "engine.first_token")) == n["admitted"]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A serve loop over a spool that holds more requests than slots, under ``TPUJOB_TRACE_DIR``."""
    root = tmp_path_factory.mktemp("serve")
    sp = Spool(root / "spool")
    for p, n in SHAPES["ragged"] + SHAPES["whole"]:
        sp.submit(prompt_len=p, max_new_tokens=n)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(obs_trace.ENV_VAR, str(root / "trace"))
        obs_trace.reset_tracer()
        stats = serve.run(config="tiny", spool_dir=str(root / "spool"), slots=3, chunk=CHUNK, block=8,
                          max_decode_len=48, max_requests=10, idle_timeout=30, log=lambda *_: None)
        spans = _spans()
    obs_trace.reset_tracer()
    return stats, spans


def test_the_boundary_is_one_span_beside_the_step_and_around_the_loops_phases(served):
    stats, spans = served
    assert stats["served"] == 10
    by_id = {e["id"]: e for e in spans}
    steps = [e for e in spans if e["name"] == "engine.step"]
    bounds = [e for e in spans if e["name"] == "serve.boundary"]
    # A boundary opens as a step returns and closes before the next begins: one a step, siblings on the thread.
    assert len(bounds) == len(steps) > 3
    assert all("parent" not in e for e in steps + bounds)
    order = sorted(steps + bounds, key=lambda e: e["id"])  # ids count the spans as they open
    assert [e["name"] for e in order] == ["engine.step", "serve.boundary"] * len(steps)
    for a, b in zip(order, order[1:]):
        assert b["ts"] >= a["ts"] + a["dur"] - 100.0, (a, b)  # microseconds; two clocks a span (wall, perf_counter)
    # While the engine is busy, the loop's phases are the boundary's children ...
    top = lambda e: e if "parent" not in e else top(by_id[e["parent"]])
    phases = [e for e in spans if e["name"] in ("serve.respond", "serve.poll", "serve.submit")]
    assert len([e for e in phases if e["name"] == "serve.respond"]) == 10
    first_step = order[0]["id"]
    for e in phases:
        if e["id"] > first_step:
            assert by_id[e["parent"]]["name"] == "serve.boundary", e
        else:  # ... and before the first step there is no boundary to be in: the engine was empty.
            assert "parent" not in e
    assert {top(e)["name"] for e in spans if e["name"].startswith("engine.")} == {"engine.step"}
    # Its self time is the loop's own overhead, its children's sum what they were.
    times = obs_trace.span_self_times(spans)
    assert 0 <= times["serve.boundary"]["self_ms"] < times["serve.boundary"]["total_ms"]


def test_with_tracing_off_a_serve_loop_writes_no_record(tmp_path, monkeypatch):
    monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
    obs_trace.reset_tracer()
    before = obs.records_emitted()
    sp = Spool(tmp_path / "spool")
    for p, n in SHAPES["ragged"]:
        sp.submit(prompt_len=p, max_new_tokens=n)
    stats = serve.run(config="tiny", spool_dir=str(tmp_path / "spool"), slots=2, chunk=CHUNK, block=8,
                      max_decode_len=48, max_requests=5, idle_timeout=30, log=lambda *_: None)
    assert stats["served"] == 5 and stats["decode_blocks"] > 0
    assert obs_trace.tracer() is None and obs.records_emitted() == before
