"""A decode step's cache write (ops/cache_write.py behind
``layer_list.write_positions`` and the llama family's ``_decode_attend``) puts
the same values at the same places as the per-row update-slices it replaced
and touches nothing else: the whole leaf compared bit for bit at the three
layer-list families' leaf shapes and at an int8 layer's four (keys, values
and their float32 scales; here under the Pallas interpreter), through a leaf
of NaNs, for a ring's ``pos``, and through each family's ``decode_block``. A
chunk keeps the scatter and traces no kernel.
"""

from __future__ import annotations

import numpy as np
import pytest

import tests.jaxenv  # noqa: F401
from pytorch_operator_tpu.models import layer_list, llama, mimo_v2, nemotron_h, phi4_flash
from pytorch_operator_tpu.ops import cache_write

# (key heads, positions, key size, value size, ring?) of a layer's leaves at the cells' sizes; the rows are few.
LEAVES = {
    "mimo_slab": (4, 4096, 192, 128, False),
    "mimo_ring": (8, 256, 192, 128, True),
    "nemotron_slab": (2, 4096, 128, 128, False),
    "phi4_slab": (10, 4096, 128, 128, False),
    "phi4_ring": (10, 640, 128, 128, True),
    "no_whole_tile": (2, 24, 24, 16, True),  # the tiny configurations' rings: the block is the whole axis
}


def update_slices(slabs, vals, idx):
    """``write_rows`` as a decode step stood before it (the llama family's own form): an update-slice a row, a leaf at a time."""
    import jax

    return [jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice(c, u, (0, i, 0)))(slab, val, idx) for slab, val in zip(slabs, vals)]


def parent_form(cache: dict, k, v, positions) -> dict:
    """``write_positions`` as it stood before the kernel: an update-slice a
    row for a decode step (a scatter for a chunk), ``pos`` set a row."""
    import jax

    idx = positions % cache["k"].shape[2]

    if idx.shape[1] == 1:
        new = dict(zip("kv", update_slices((cache["k"], cache["v"]), (k, v), idx[:, 0])))
    else:
        new = {leaf: jax.vmap(lambda c, u, i: c.at[:, i].set(u))(cache[leaf], vals, idx) for leaf, vals in (("k", k), ("v", v))}
    if "pos" in cache:
        new["pos"] = jax.vmap(lambda c, u, i: c.at[i].set(u))(cache["pos"], positions, idx)
    return new


def ragged(T: int, ring: bool):
    """A row each at: position 0, the last, a tile's first and last, a second
    row in that tile, the tile after; for a ring, positions past its length."""
    t = cache_write.tile(T)
    at = [0, T - 1, t % T, (2 * t - 1) % T, (t + 3) % T, (2 * t) % T]
    if ring:
        at += [T, 3 * T + t + 1, 5 * T - 1]
    return np.asarray(at, np.int32)[:, None]


def bits(a):
    a = np.asarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def layer_cache(name, dtype, rng, fill=None):
    """A layer's cache at ``LEAVES[name]`` with every position filled (or all
    ``fill``), the incoming keys and values, and the rows' positions."""
    import jax.numpy as jnp

    Hk, T, dk, dv, ring = LEAVES[name]
    positions = ragged(T, ring)
    B = len(positions)
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), dtype)  # noqa: E731
    held = (lambda *shape: jnp.full(shape, fill, dtype)) if fill is not None else draw
    cache = {"k": held(B, Hk, T, dk), "v": held(B, Hk, T, dv)}
    if ring:
        cache["pos"] = jnp.asarray(rng.integers(-1, 5 * T, (B, T)), jnp.int32)
    return cache, draw(B, Hk, 1, dk), draw(B, Hk, 1, dv), jnp.asarray(positions)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", list(LEAVES))
def test_a_decode_steps_write_is_the_parent_forms_bit_for_bit(name, dtype):
    import jax

    cache, k, v, positions = layer_cache(name, dtype, np.random.default_rng(0))
    got = jax.jit(layer_list.write_positions)(cache, k, v, positions)
    want = jax.jit(parent_form)(cache, k, v, positions)
    assert sorted(got) == sorted(want)
    for leaf in want:
        assert got[leaf].dtype == want[leaf].dtype and got[leaf].shape == want[leaf].shape
        assert np.array_equal(bits(got[leaf]), bits(want[leaf])), leaf
    # and the places are the ones meant: row b's position % T holds the new values
    T = cache["k"].shape[2]
    for b, p in enumerate(np.asarray(positions)[:, 0] % T):
        assert np.array_equal(bits(got["k"][b, :, p]), bits(k[b, :, 0]))
        assert np.array_equal(bits(got["v"][b, :, p]), bits(v[b, :, 0]))


@pytest.mark.parametrize("name", ["nemotron_slab", "mimo_ring", "no_whole_tile"])
def test_no_other_position_is_computed_with_or_rewritten(name):
    """A leaf of NaNs: what the write did not mean to touch is NaN still (bit
    for bit), and what it wrote is finite, so no held position leaked into a
    new one. ``pos`` keeps every entry but the rows' own."""
    import jax

    cache, k, v, positions = layer_cache(name, "bfloat16", np.random.default_rng(1), fill=float("nan"))
    got = jax.jit(layer_list.write_positions)(cache, k, v, positions)
    T = cache["k"].shape[2]
    at = np.asarray(positions)[:, 0] % T
    for leaf, new in (("k", k), ("v", v)):
        out, before = np.asarray(got[leaf]), np.asarray(cache[leaf])
        written = np.zeros(out.shape, bool)
        written[np.arange(len(at)), :, at] = True
        assert np.isfinite(out[written].astype(np.float32)).all()
        assert np.array_equal(bits(out)[~written], bits(before)[~written])
        assert np.array_equal(bits(out[np.arange(len(at)), :, at]), bits(np.asarray(new)[:, :, 0]))
    if "pos" in cache:
        out, before = np.asarray(got["pos"]), np.asarray(cache["pos"])
        assert np.array_equal(out[np.arange(len(at)), at], np.asarray(positions)[:, 0])
        mask = np.ones(out.shape, bool)
        mask[np.arange(len(at)), at] = False
        assert np.array_equal(out[mask], before[mask])


def test_a_decode_step_is_the_kernel_under_its_scope_and_a_chunk_traces_none():
    import jax
    import jax.numpy as jnp

    cache, k, v, positions = layer_cache("no_whole_tile", "float32", np.random.default_rng(2))
    step = jax.make_jaxpr(layer_list.write_positions)(cache, k, v, positions)
    kernels = [e for e in step.eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1 and "scatter" not in str(step)  # keys and values through one call (the compile pins read the chip's program)
    scoped = {e.primitive.name for e in step.eqns if "cache_write" in str(e.source_info.name_stack)}
    assert {"pallas_call", "iota", "eq"} <= scoped, scoped  # the leaves' kernel and the select over ``pos``
    # A chunk of 5 positions a row, wrapping a ring inside it: the scatter as it was, and the parent form's result.
    B, Hk, T, dk = cache["k"].shape
    rng = np.random.default_rng(3)
    ck = jnp.asarray(rng.normal(size=(B, Hk, 5, dk)), jnp.float32)
    cv = jnp.asarray(rng.normal(size=(B, Hk, 5, cache["v"].shape[3])), jnp.float32)
    cpos = jnp.asarray(np.asarray(positions) + np.arange(5)[None, :], jnp.int32)
    assert "pallas_call" not in str(jax.make_jaxpr(layer_list.write_positions)(cache, ck, cv, cpos))
    got, want = layer_list.write_positions(cache, ck, cv, cpos), parent_form(cache, ck, cv, cpos)
    assert all(np.array_equal(bits(got[leaf]), bits(want[leaf])) for leaf in want)


# Positions of an int8 layer (the llama family's ``--kv-quantize int8``): the InternLM2 cells' 4,096 (a scale leaf
# seen as [.., 32, 128], blocks of 1,024 positions), lengths 1,024 divides once and twice, one that 128 divides and
# 1,024 does not and the tiny caches' (the scale's block is its whole axis; at 40 the int8 tile of 32 is too).
INT8_LENGTHS = [4096, 2048, 1024, 1152, 64, 40]


def int8_places(L: int):
    """A row each at: position 0, a tile of 32's last and the next one's first, a row of 128 lanes' last and the
    next one's first, a block of 1,024's last and the next one's first, a second row inside that block, the last."""
    return np.asarray(sorted({p for p in (0, 31, 32, 127, 128, 1023, 1024, 1500, L - 1) if p < L}), np.int32)


def int8_layer(L: int, rng, poisoned: bool = False, heads: int = 2, size: int = 128):
    """An int8 layer's four leaves in ``_decode_attend``'s order (keys, their scales, values, theirs) with every
    position filled (``poisoned``: a sentinel in the payloads, NaN in the scales), a step's values, the rows' places."""
    import jax.numpy as jnp

    at = int8_places(L)
    B = len(at)
    payload = lambda n: jnp.asarray(np.full((B, heads, n, size), -77) if poisoned and n == L else rng.integers(-127, 128, (B, heads, n, size)), jnp.int8)  # noqa: E731
    scale = lambda n: jnp.asarray(np.full((B, heads, n, 1), np.nan) if poisoned and n == L else rng.uniform(1e-3, 1.0, (B, heads, n, 1)), jnp.float32)  # noqa: E731
    return [payload(L), scale(L), payload(L), scale(L)], [payload(1), scale(1), payload(1), scale(1)], jnp.asarray(at)


@pytest.mark.parametrize("L", INT8_LENGTHS)
def test_an_int8_layers_write_is_the_update_slices_bit_for_bit(L):
    import jax

    slabs, vals, idx = int8_layer(L, np.random.default_rng(5))
    got = jax.jit(cache_write.write_rows)(slabs, vals, idx)
    want = jax.jit(update_slices)(slabs, vals, idx)
    assert len(got) == len(want) == 4
    for leaf, (a, b, new) in enumerate(zip(got, want, vals)):
        assert a.dtype == b.dtype and a.shape == b.shape == slabs[leaf].shape
        assert np.array_equal(bits(a), bits(b)), leaf
        # and the places are the ones meant
        for row, p in enumerate(np.asarray(idx)):
            assert np.array_equal(bits(a[row, :, p]), bits(new[row, :, 0])), (leaf, row, p)


@pytest.mark.parametrize("L", [4096, 1152, 40])
def test_an_int8_layers_other_positions_keep_their_bits(L):
    """Scale leaves of NaNs and payloads of a sentinel: outside the rows' own places every bit is what it was
    (whichever view of the scales the kernel took), and what was written is the new value alone."""
    import jax

    slabs, vals, idx = int8_layer(L, np.random.default_rng(6), poisoned=True)
    got = jax.jit(cache_write.write_rows)(slabs, vals, idx)
    at = np.asarray(idx)
    for leaf, (out, before, new) in enumerate(zip(got, slabs, vals)):
        out, before = np.asarray(out), np.asarray(before)
        written = np.zeros(out.shape, bool)
        written[np.arange(len(at)), :, at] = True
        assert np.array_equal(bits(out)[~written], bits(before)[~written]), leaf
        assert np.array_equal(bits(out[np.arange(len(at)), :, at]), bits(np.asarray(new)[:, :, 0])), leaf
        if out.dtype == np.float32:
            assert np.isfinite(out[written]).all() and np.isnan(out[~written]).all()


def test_an_int8_layer_goes_through_one_call_whichever_view_its_scales_take():
    import jax

    for L in (4096, 40):
        slabs, vals, idx = int8_layer(L, np.random.default_rng(7), heads=1, size=8)
        step = jax.make_jaxpr(cache_write.write_rows)(slabs, vals, idx)
        kernels = [e for e in step.eqns if e.primitive.name == "pallas_call"]
        assert len(kernels) == 1 and "scatter" not in str(step)
        # the scales go in as rows of 128 lanes where blocks of 1,024 positions divide them, else as their one row
        seen = [v.aval.shape for v in kernels[0].invars if v.aval.shape[:1] == (len(idx),) and v.aval.dtype == np.float32 and v.aval.shape[2:] != (1, 1)]
        assert seen == [(len(idx), 1, *((L // 128, 128) if L % 1024 == 0 else (1, L)))] * 2, seen


FAMILIES = {
    "mimo": lambda: mimo_v2.mimo_v2_tiny(decode=True, max_decode_len=64),
    "nemotron": lambda: nemotron_h.nemotron_h_tiny(decode=True, max_decode_len=64),
    "phi4": lambda: phi4_flash.phi4_flash_tiny(decode=True, max_decode_len=64),
    # the llama family writes through ``write_rows`` itself: a plain cache's two leaves, an int8 one's four
    "llama": lambda: llama.llama_tiny(decode=True, max_decode_len=64),
    "llama_int8": lambda: llama.llama_tiny(decode=True, max_decode_len=64, kv_quantize="int8"),
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_whole_decode_block_leaves_the_cache_the_parent_form_leaves(family, monkeypatch):
    """The engine's own ``decode_block`` over the family's tiny configuration,
    five steps from ragged positions (a parked row among them, rings wrapping):
    every leaf of the cache and every token equal what the program gives with
    the parent's write in place of the kernel."""
    import jax
    import jax.numpy as jnp

    from pytorch_operator_tpu.ops.sampling import make_sampler
    from pytorch_operator_tpu.serving.engine import programs

    SLOTS, CHUNK, BLOCK, STEPS = 5, 16, 8, 5
    model = FAMILIES[family]().serving_model()
    params = model.init_params(jax.random.key(0))
    rng = np.random.default_rng(4)

    def filled():
        # Every position holds something: a write that strayed would show.
        return jax.tree.map(
            lambda a: jnp.asarray(
                rng.integers(0, 40, a.shape) if a.dtype == jnp.int32 else rng.integers(-127, 128, a.shape) if a.dtype == jnp.int8
                else rng.normal(size=a.shape) * 0.1, a.dtype),
            model.init_cache(SLOTS, CHUNK),
        )

    cache = filled()
    tok = jnp.asarray([3, 9, 27, 81, 243], jnp.int32)
    pos = jnp.asarray([0, 15, 16, 40, 58], jnp.int32)
    active = jnp.asarray([True, True, False, True, True])

    def run():
        progs = programs(model, slots=SLOTS, chunk=CHUNK, block=BLOCK, sample=make_sampler(0.0, 0, 1.0))
        mine, counts = jax.tree.map(jnp.copy, (cache, model.counts))  # the program takes both for its own
        toks, new, *_ = progs.decode_block(params, mine, counts, tok, pos, active, jax.random.key(1), jnp.int32(STEPS))
        return np.asarray(toks), jax.tree.map(np.asarray, new)

    toks, new = run()
    monkeypatch.setattr(layer_list, "write_positions", parent_form)
    monkeypatch.setattr(cache_write, "write_rows", update_slices)  # the llama family's: looked up at each trace
    if family == "mimo":
        monkeypatch.setattr(mimo_v2, "write_positions", parent_form)
    want_toks, want = run()
    assert np.array_equal(toks, want_toks)
    leaves, want_leaves = jax.tree.leaves_with_path(new), jax.tree.leaves(want)
    assert len(leaves) == len(want_leaves) > 0
    for (path, a), b in zip(leaves, want_leaves):
        assert np.array_equal(bits(a), bits(b)), jax.tree_util.keystr(path)
    # the steps did write: the active rows' slabs differ from what they held
    before = jax.tree.leaves(jax.tree.map(np.asarray, cache))
    assert any(not np.array_equal(a, b) for (_, a), b in zip(leaves, before))
