"""The engine's record as it stood at the newest arrival
(``ServingEngine.submit`` copies it; ``stats()`` gives it as ``fed_*``): the
engine under arrivals, apart from the engine emptying its slots."""

from __future__ import annotations

import dataclasses
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.serving import Request, ServingEngine

FED = ("fed_s", "fed_decode_blocks", "fed_decode_steps", "fed_decode_tokens", "fed_slot_occupancy_pct",
       "fed_decode_yield_pct", "fed_decode_tokens_per_sec", "fed_host_gap_s")


@pytest.fixture(scope="module")
def engine():
    import flax.linen as nn
    import jax

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=48)
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32)
        )["params"]
    )
    return ServingEngine(cfg, params, slots=3, chunk=8, block=4)


def _submit(eng, prefix, shapes):
    rng = np.random.default_rng(len(prefix))
    for i, (p, n) in enumerate(shapes):
        eng.submit(Request(id=f"{prefix}{i}", prompt=rng.integers(0, 256, (p,)).astype(np.int32),
                           max_new_tokens=n, submit_time=time.time()))


@pytest.fixture
def drained(engine):
    """Three requests, three steps, three more requests (the newest arrival), then nothing until all are done."""
    t0 = time.perf_counter()  # before the reset: the test's clock brackets the engine's, whatever lies between the two calls
    engine.reset_stats()
    _submit(engine, "a", [(5, 20), (13, 24), (8, 16)])
    for _ in range(3):
        engine.step()
    at_arrival = engine.stats()
    _submit(engine, "bb", [(7, 9), (3, 30), (11, 12)])
    fed_by = time.perf_counter() - t0
    engine.run_until_drained()
    return engine.stats(), at_arrival, fed_by, time.perf_counter() - t0


def test_the_fed_record_is_the_whole_record_as_it_stood_at_the_newest_arrival(drained):
    stats, at_arrival, _, _ = drained
    # Every fed key is the whole record's of that moment, by the same expression.
    for key in FED[1:]:
        assert stats[key] == at_arrival[key[4:]], key
    assert stats["fed_decode_blocks"] == 3


@pytest.mark.parametrize("key", ["decode_blocks", "decode_steps", "decode_tokens", "host_gap_s"])
def test_the_whole_record_runs_on_through_the_drain(drained, key):
    stats = drained[0]
    assert 0 < stats[f"fed_{key}"] < stats[key]


def test_the_slots_were_fuller_under_arrivals_than_over_the_drain(drained):
    stats = drained[0]
    assert stats["fed_slot_occupancy_pct"] == 100.0 > stats["slot_occupancy_pct"] > 0
    assert 0 < stats["fed_decode_yield_pct"] <= 100 and stats["fed_decode_tokens_per_sec"] > 0


def test_fed_s_runs_from_the_reset_to_the_newest_arrival(drained):
    stats, _, fed_by, whole = drained
    assert 0 < stats["fed_s"] <= fed_by < whole
    assert stats["fed_host_gap_s"] <= stats["fed_s"]


@pytest.mark.parametrize("key", FED)
def test_reset_clears_the_fed_record(engine, drained, key):
    assert drained[0][key]
    engine.reset_stats()
    assert not engine.stats()[key]  # 0, 0.0, or None where the whole record's own reads None
