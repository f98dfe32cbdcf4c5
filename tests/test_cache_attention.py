"""Attention against a cache reads the filled prefix (ops/cache_attention.py)
and is the whole-slab masked product all the same: the op alone around every
block's edge, through both families' call sites, and through the
engine, where a slot whose last occupant stood deep must not hold the
bound up. A decode step over a plain slab is a kernel that reads each row to
that row's own depth (here under the Pallas interpreter); an int8 slab and a
prefill chunk keep the loop to the deepest query.
"""

from __future__ import annotations

import dataclasses
import functools
import time

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import llama as llama_lib
from pytorch_operator_tpu.models import mimo_v2
from pytorch_operator_tpu.ops import cache_attention as ca
from pytorch_operator_tpu.serving import Request, ServingEngine

L = 64
BLOCK = ca.block(L)
LENGTHS = tuple(range(BLOCK, L + 1, BLOCK))  # what the op can read of the slab
# (kv heads, queries a kv head, key size, value size, cache): the llama
# family's int8 cache with scales and its plain one, the layer-pattern
# family's two head sizes.
KINDS = {
    "int8_scales": (2, 2, 16, 16, "int8"),
    "plain_f32": (2, 2, 16, 16, "float32"),
    "plain_bf16": (2, 2, 16, 16, "bfloat16"),
    "heads_192_128": (2, 4, 24, 16, "float32"),
}


def _whole_slab(q, positions, k, v, k_scale=None, v_scale=None):
    """What both call sites computed before the bound: every one of the
    slab's positions scored, the dead ones masked."""
    import jax
    import jax.numpy as jnp

    dtype = q.dtype
    scores = jnp.einsum(
        "bskgd,bktd->bkgst", q, k.astype(dtype), preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(q.shape[-1]))
    if k_scale is not None:
        scores = scores * k_scale.squeeze(-1)[:, :, None, None, :]
    visible = jnp.arange(k.shape[2])[None, None, :] <= positions[:, :, None]
    scores = jnp.where(visible[:, None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    if v_scale is not None:
        probs = (probs * v_scale.squeeze(-1)[:, :, None, None, :]).astype(dtype)
    return jnp.einsum("bkgst,bktd->bskgd", probs, v.astype(dtype))


@functools.lru_cache(maxsize=None)
def _programs():
    import jax

    return jax.jit(ca.cache_attention), jax.jit(_whole_slab)


def _inputs(kind, positions, seed=0):
    """(q, positions, k, v[, scales]) with every position of the slab
    filled: what lies past a query's position must not count."""
    import jax.numpy as jnp

    K, G, dk, dv, cache = KINDS[kind]
    B, S = positions.shape
    rng = np.random.default_rng(seed)
    qdtype = jnp.bfloat16 if cache == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(B, S, K, G, dk)), qdtype)
    if cache == "int8":
        k = jnp.asarray(rng.integers(-127, 128, (B, K, L, dk)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (B, K, L, dv)), jnp.int8)
        scales = tuple(jnp.asarray(rng.uniform(0.005, 0.02, (B, K, L, 1)), jnp.float32) for _ in range(2))
    else:
        k = jnp.asarray(rng.normal(size=(B, K, L, dk)), cache)
        v = jnp.asarray(rng.normal(size=(B, K, L, dv)), cache)
        scales = ()
    return (q, jnp.asarray(positions, jnp.int32), k, v, *scales)


def _decode_positions(deepest, rows=3, seed=0):
    """Rows at mixed depths, the deepest at ``deepest``."""
    pos = np.random.default_rng(seed).integers(0, deepest + 1, (rows, 1))
    pos[1, 0] = deepest
    return pos


def _chunk_positions(last, chunk=4):
    """One row's chunk that starts mid-prompt and ends at ``last``."""
    return np.arange(max(last - chunk + 1, 0), last + 1)[None, :]


EDGES = [
    (n, off) for n in LENGTHS for off in (-1, 0, 1) if n + off <= L
]  # positions needed: one below, at and one above every length


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("site", ["decode", "chunk"])
@pytest.mark.parametrize("length, off", EDGES)
def test_equals_the_whole_slab_masked_product(kind, site, length, off):
    needed = length + off
    positions = (_decode_positions if site == "decode" else _chunk_positions)(needed - 1)
    args = _inputs(kind, positions, seed=needed)
    op, whole = _programs()
    got, want = np.asarray(op(*args), np.float32), np.asarray(whole(*args), np.float32)
    # Float32 differs by the order of its sums alone (a running softmax over
    # blocks); a bfloat16 result by a rounding of the same sums at most.
    tol = 2e-2 if KINDS[kind][4] == "bfloat16" else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@pytest.mark.parametrize("needed", sorted({1, L, *(n + off for n, off in EDGES)}))
def test_the_bound_is_the_fewest_blocks_that_hold_what_is_needed(needed):
    want = min(n for n in LENGTHS if n >= needed)
    assert ca.attended(needed, L) == want == ca.blocks_needed(needed, L) * BLOCK
    assert list(ca.attended(np.array([needed, 1]), L)) == [want, BLOCK]


def test_a_slab_is_read_in_eighths_or_whole():
    assert ca.block(4096) == 512  # the chat cell's prompts (median 256) must find a short prefix
    for slab, want in ((128, 16), (48, 6), (100, 100), (9, 9), (1, 1)):
        assert ca.block(slab) == want and slab % want == 0
        assert ca.attended(slab, slab) == slab and ca.attended(1, slab) == want


@pytest.mark.parametrize("site", ["decode", "chunk"])
@pytest.mark.parametrize("length", LENGTHS[:-1])
def test_nothing_past_the_bound_is_read(site, length):
    """NaN in every position past the bound: the whole-slab product would
    carry it into the result (0 x NaN), the bounded one never reads it."""
    import jax.numpy as jnp

    positions = (_decode_positions if site == "decode" else _chunk_positions)(length - 1)
    q, pos, k, v = _inputs("plain_f32", positions, seed=length)
    op, whole = _programs()
    want = np.asarray(whole(q, pos, k, v))
    poison = lambda a: a.at[:, :, length:].set(jnp.nan)  # noqa: E731
    got = np.asarray(op(q, pos, poison(k), poison(v)))
    assert np.isfinite(got).all() and not np.isfinite(np.asarray(whole(q, pos, k, poison(v)))).any()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ---- a decode step over a plain slab: the kernel with per-row lengths ----

# (kv heads, queries a kv head, key size, value size) of the three layer-list
# families' cells: Phi-4-mini-flash's pairs, MiMo-V2.5's full layers,
# Nemotron-3-Nano's attention layers.
FAMILY_SHAPES = {"phi4_flash": (10, 4, 128, 128), "mimo_v2": (4, 16, 192, 128), "nemotron_h": (2, 16, 128, 128)}
SLAB = 256  # positions of the slabs below: blocks of 32


def _ragged(case):
    T = ca.block(SLAB)
    return {
        "every_edge": [0, 1, T - 1, T, T + 1, SLAB - 1],
        "one_deep_among_shallow": [3, 0, 5 * T + 7, 1, T - 1, 2],
        "every_row_empty": [0] * 6,
    }[case]


def _plain_float32(q, positions, k, v):
    """The whole slab, float32 throughout, one softmax: no blocks, no running sums."""
    import jax
    import jax.numpy as jnp

    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    scores = jnp.einsum("bskgd,bktd->bkgst", q, k, precision="highest") / np.sqrt(q.shape[-1])
    visible = jnp.arange(k.shape[2])[None, None, :] <= positions[:, :, None]
    probs = jax.nn.softmax(jnp.where(visible[:, None, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgst,bktd->bskgd", probs, v, precision="highest")


def _loop(*args):
    """The same call through the loop to the deepest query."""
    import jax

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ca, "reads_per_row", lambda *a, **kw: False)
        return jax.jit(lambda *a: ca.cache_attention(*a))(*args)  # a function of its own: traced anew


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", ["every_edge", "one_deep_among_shallow", "every_row_empty"])
@pytest.mark.parametrize("family", FAMILY_SHAPES)
def test_the_decode_kernel_equals_the_loop_and_the_plain_softmax_at_ragged_depths(family, case, dtype):
    import jax
    import jax.numpy as jnp

    K, G, dk, dv = FAMILY_SHAPES[family]
    depths = _ragged(case)
    rng = np.random.default_rng(len(family) + len(case))
    q = jnp.asarray(rng.normal(size=(len(depths), 1, K, G, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(len(depths), K, SLAB, dk)), dtype)
    v = jnp.asarray(rng.normal(size=(len(depths), K, SLAB, dv)), dtype)
    positions = jnp.asarray(depths, jnp.int32)[:, None]
    assert "pallas_call" in str(jax.make_jaxpr(ca.cache_attention)(q, positions, k, v))
    got = jax.jit(ca.cache_attention)(q, positions, k, v)
    assert got.shape == (len(depths), 1, K, G, dv) and got.dtype == q.dtype
    got = np.asarray(got, np.float32)
    # The loop's mathematics block for block; the backend may order a product's sums otherwise
    # for a row alone than for the batch (float32), which a bfloat16 result rounds once more.
    tol = 1e-2 if dtype == "bfloat16" else 2e-6
    np.testing.assert_allclose(got, np.asarray(_loop(q, positions, k, v), np.float32), rtol=tol, atol=tol)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(got, np.asarray(_plain_float32(q, positions, k, v)), rtol=tol, atol=tol)


def test_a_decode_step_reads_nothing_past_each_rows_own_block():
    """NaN past every row's own last needed block, inside what the deepest
    row needs: the loop to the deepest query reads it (0 x NaN), the kernel
    does not."""
    import jax.numpy as jnp

    depths = np.array([0, BLOCK - 1, BLOCK, 3 * BLOCK + 2, L - 1])
    q, pos, k, v = _inputs("plain_f32", depths[:, None], seed=3)
    own = ca.attended(depths + 1, L)[:, None, None, None]
    poison = lambda a: jnp.where(jnp.arange(L)[None, None, :, None] >= own, jnp.nan, a)  # noqa: E731
    op, whole = _programs()
    got = np.asarray(op(q, pos, poison(k), poison(v)))
    assert np.isfinite(got).all() and not np.isfinite(np.asarray(_loop(q, pos, poison(k), poison(v)))[:-1]).any()
    np.testing.assert_allclose(got, np.asarray(whole(q, pos, k, v)), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("call", ["int8_decode", "int8_chunk", "plain_chunk_in_slot", "plain_chunk_no_slot", "plain_decode_in_slot"])
def test_an_int8_slab_and_every_slot_call_still_trace_the_loop(call):
    """Only a decode step over a plain slab is the kernel: the int8 family's
    programs and every prefill chunk trace as they did."""
    import jax
    import jax.numpy as jnp

    kind = "int8_scales" if call.startswith("int8") else "plain_bf16"
    positions = _decode_positions(20) if call.endswith("decode") else _chunk_positions(20)
    q, pos, *cache = _inputs(kind, positions if "slot" not in call else positions[:1, :1 if "decode" in call else None])
    slot = jnp.int32(1) if "slot" in call else None
    text = str(jax.make_jaxpr(lambda *a: ca.cache_attention(*a, slot=slot))(q, pos, *cache))
    assert "pallas_call" not in text and "while" in text
    assert ca.reads_per_row() and not ca.reads_per_row(quantized=True)
    assert not ca.reads_per_row(in_slot=True) and not ca.reads_per_row(queries_a_row=4)


def test_a_row_at_the_parking_position_reads_the_whole_slab():
    positions = _decode_positions(7)
    positions[2, 0] = L - 1  # a row clamped there lifts the bound to L
    args = _inputs("int8_scales", positions)
    op, whole = _programs()
    np.testing.assert_allclose(np.asarray(op(*args)), np.asarray(whole(*args)), rtol=2e-5, atol=2e-5)
    assert ca.attended(L, L) == L


# ---- through the two call sites and the engine ----


def _llama(**over):
    import flax.linen as nn
    import jax

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=L, **over)
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32)
        )["params"]
    )
    return cfg, params


def _mimo():
    import jax

    cfg = mimo_v2.mimo_v2_tiny(decode=True, max_decode_len=L)
    return cfg, mimo_v2.init_params(cfg, jax.random.key(0))


FAMILIES = {
    "llama_int8_cache": lambda: _llama(kv_quantize="int8"),
    "llama_plain_cache": _llama,
    "mimo_two_cache_kinds": _mimo,
}


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 256, (n,)).astype(np.int32)


def _engine(cfg, params, **over):
    return ServingEngine(cfg, params, **{"slots": 3, "chunk": 8, "block": 4, **over})


def _submit(eng, jobs):
    for i, (prompt, new) in enumerate(jobs):
        eng.submit(Request(id=f"r{i}", prompt=prompt, max_new_tokens=new, submit_time=time.time()))


@pytest.mark.parametrize("family", FAMILIES)
def test_engine_tokens_equal_a_whole_slab_engines(family, monkeypatch):
    """Greedy tokens of requests whose depths cross several blocks, four
    through three slots, against the same engine built with the whole slab
    as its only block."""
    cfg, params = FAMILIES[family]()
    jobs = [(_prompt(5, 1), 20), (_prompt(30, 2), 12), (_prompt(13, 3), 25), (_prompt(41, 4), 9)]

    def serve():
        eng = _engine(cfg, params)
        _submit(eng, jobs)
        done = {r.id: r.tokens for r in eng.run_until_drained()}
        return [done[f"r{i}"] for i in range(len(jobs))], eng.stats()

    bounded, stats = serve()
    monkeypatch.setattr(ca, "BLOCKS", 1)  # one block: the whole slab at every step
    assert ca.block(L) == L
    whole, whole_stats = serve()
    assert bounded == whole and [len(t) for t in bounded] == [new for _, new in jobs]
    rows = stats["decode_row_steps"]
    assert whole_stats["decode_attended_positions"] == rows * L
    assert stats["decode_live_positions"] < stats["decode_attended_positions"] < rows * L
    chunks = stats["prefill_chunks"]
    assert whole_stats["prefill_attended_positions"] == chunks * L
    assert stats["prefill_tokens"] < stats["prefill_attended_positions"] < chunks * L


@pytest.mark.parametrize("family", FAMILIES)
def test_an_empty_slot_whose_last_occupant_stood_deep_does_not_hold_the_bound_up(family):
    """A deep request leaves while a shallow one runs on: the parked row
    stands at position 0 in the program, the shallow row's tokens are those
    of an engine that never held the deep one, and from then on each of its
    steps reads the shallow row's own short prefix."""
    cfg, params = FAMILIES[family]()
    deep, shallow = (_prompt(50, 5), 3), (_prompt(4, 6), 24)
    eng = _engine(cfg, params, slots=2)
    _submit(eng, [deep, shallow])
    done = []
    while len(done) < 1:
        done += eng.step()
    assert done[0].id == "r0" and eng.slots_free == 1
    before = eng.stats()
    done += eng.run_until_drained()
    after = eng.stats()
    assert int(np.asarray(eng._pos)[0]) == 0  # slot 0, empty since the deep request left
    row_steps = after["decode_row_steps"] - before["decode_row_steps"]
    attended = after["decode_attended_positions"] - before["decode_attended_positions"]
    # The shallow row never passes position 4 + 24 <= 32; the deep one stood past 48.
    assert row_steps > 0 and attended <= row_steps * ca.attended(32, L) < row_steps * ca.attended(51, L)

    alone = _engine(cfg, params, slots=2)
    _submit(alone, [shallow])
    (only,) = alone.run_until_drained()
    assert {r.id: r.tokens for r in done}["r1"] == only.tokens


def test_decode_attended_positions_follow_the_rows_positions_step_by_step():
    """One row from position 6: each step reads the blocks that hold the
    row's position, summed over the dispatch's steps."""
    cfg, params = _llama()
    eng = _engine(cfg, params, slots=1, block=64)
    _submit(eng, [(_prompt(6, 7), 30)])
    eng.run_until_drained()
    s = eng.stats()
    # The first token comes from the prefill; 29 decode steps write positions 6 .. 34.
    want = sum(int(ca.attended(p + 1, L)) for p in range(6, 6 + 29))
    assert s["decode_row_steps"] == 29 and s["decode_attended_positions"] == want
    assert s["prefill_attended_positions"] == ca.attended(8, L)


@pytest.mark.parametrize("family", FAMILIES)
def test_decode_attended_positions_round_as_the_program_reads(family):
    """A deep row and a shallow one decode side by side: a family whose
    decode step is the kernel is charged each row's own blocks, the int8
    family the deepest row's for both."""
    cfg, params = FAMILIES[family]()
    eng = _engine(cfg, params, slots=2, chunk=8, block=64)
    per_row = eng.model.decode_reads_per_row
    assert per_row == (family != "llama_int8_cache")
    _submit(eng, [(_prompt(41, 8), 9), (_prompt(3, 9), 9)])
    eng.run_until_drained()
    s = eng.stats()
    # The first token comes from the prefill; 8 decode steps write positions 41 .. 48 and 3 .. 10.
    deep = sum(int(ca.attended(p + 1, L)) for p in range(41, 49))
    shallow = sum(int(ca.attended(p + 1, L)) for p in range(3, 11))
    assert s["decode_row_steps"] == 16 and s["decode_live_positions"] < deep + shallow < 2 * deep
    assert s["decode_attended_positions"] == (deep + shallow if per_row else 2 * deep)


def test_the_final_metrics_record_carries_the_two_counters(tmp_path, monkeypatch):
    """``serve.run``'s last ``metrics`` record (what the benchmark's readers
    get) holds every number of ``engine.stats()``, these two among them."""
    from pytorch_operator_tpu.runtime import rendezvous
    from pytorch_operator_tpu.serving import Spool
    from pytorch_operator_tpu.workloads import serve

    records = []
    monkeypatch.setattr(rendezvous, "report_metrics", lambda step, **m: records.append(m))
    Spool(tmp_path / "spool").submit(prompt=[1, 2, 3, 4, 5], max_new_tokens=6)
    stats = serve.run(
        config="tiny", spool_dir=str(tmp_path / "spool"), slots=2, chunk=8, block=4,
        max_decode_len=L, max_requests=1, idle_timeout=60, log=lambda *_: None,
    )
    assert stats["served"] == 1
    for name in ("decode_attended_positions", "prefill_attended_positions"):
        assert records[-1][name] == stats[name] > 0
