"""Attention of queries against a key/value cache, reading the cache's
filled prefix and not the whole slab.

Both served families keep a row's keys and values in a slab of
``max_decode_len`` positions (models/llama.py ``Attention._cache_attend``,
models/mimo_v2.py full layers) of which the traffic fills a part. A decode
step or a prefill chunk needs positions ``[0, n)`` where ``n`` is one past
the last position any of its queries stands at, so the program computes
``n`` from ``positions`` and walks the slab in blocks (:func:`block`) up to
the one that holds ``n``: a loop with a traced trip count and a running
softmax, one compiled body whatever is filled. Inside the blocks read the
mathematics is the whole slab's: the same dtypes, float32 scores, a float32
softmax (carried as running maximum, sum and weighted values, normalised
once at the end), every position ``col <= row`` attended, nothing
approximated. Rows that hold no request must stand at position 0
(serving/engine.py ``decode_block``), or their stale positions hold the
bound up.

The engine counts what the bound saved with the same rounding
(:func:`attended`), on the host, from the positions it already holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

# Blocks a slab is read in. Chosen on the chip at 4096 positions (PERF.md
# section 6, PR 29): an iteration costs about 7 us beside its reads, so
# 512 positions a block beat 256 at both families' decode shapes from 512
# filled up, and a static prefix under ``lax.switch`` (30-40 us a
# conditional) up to 2048.
BLOCKS = 8


def block(L: int) -> int:
    """Positions a block of a slab of ``L`` holds: an eighth of the slab,
    or all of it where eighths do not divide it."""
    return L // BLOCKS if L % BLOCKS == 0 else L


def blocks_needed(needed, L: int):
    """Blocks that hold positions ``[0, needed)``. ``needed`` may be a
    Python int, a numpy array or a traced value: the program's trip count
    and the engine's counters round alike."""
    return (needed + block(L) - 1) // block(L)


def attended(needed, L: int):
    """Positions of the slab read when ``needed`` are live (host side;
    ``needed`` an int or an integer array)."""
    return blocks_needed(np.asarray(needed), L) * block(L)


def cache_attention(q, positions, k, v, k_scale=None, v_scale=None, *, slot=None):
    """Grouped-query attention of ``q [B, S, K, G, dk]``, whose queries
    stand at ``positions [B, S]``, against a cache ``k [B, K, L, dk]`` /
    ``v [B, K, L, dv]`` that already holds the queries' own keys and values:
    position ``t`` of a row is visible to a query at ``p`` iff ``t <= p``.
    ``k_scale`` / ``v_scale`` ``[B, K, L, 1]`` float32 are an int8 cache's
    per-position scales. Float32 scores and softmax; the result
    ``[B, S, K, G, dv]`` in the queries' dtype.

    With ``slot`` (a traced scalar) the queries are one row (``B == 1``)
    whose cache is row ``slot`` of slabs ``[slots, K, L, d]``: each block
    is cut out of that row where it lies, so the row is never an array of
    its own (a prefill chunk in the serving engine).

    Only the blocks up to the deepest query's position are read."""
    B, S, K, G, dk = q.shape
    L, dv, dtype = k.shape[2], v.shape[-1], q.dtype
    T = block(L)
    row = positions[:, :, None]  # query position [B, S, 1]
    lowest = jnp.finfo(jnp.float32).min

    def cut(slab, i):
        if slot is None:
            return jax.lax.dynamic_slice_in_dim(slab, i * T, T, axis=2)
        return jax.lax.dynamic_slice(slab, (slot, 0, i * T, 0), (1, K, T, slab.shape[-1]))

    def per_position(scale, i):  # [B, K, T, 1] -> over scores [B, K, G, S, T]
        return cut(scale, i).squeeze(-1)[:, :, None, None, :]

    def one_block(i, carry):
        top, total, out = carry  # running maximum, sum, weighted values
        # The keys in the slab's own layout (head size minor): left to
        # itself the compiler reads them transposed inside the loop, and
        # copies the whole slab into that layout at every call (PERF.md
        # section 6, PR 29).
        kb = with_layout_constraint(cut(k, i), Layout(major_to_minor=(0, 1, 2, 3)))
        vb = cut(v, i)
        if k_scale is not None:
            # Convert-ONLY on the slabs (int8 -> 256 levels is exact in a
            # bf16 mantissa); the per-position scales fold into the small
            # score and probability tensors after the products. A fused
            # convert+scale on the slab defeats operand fusion and
            # materialises a full-precision copy a layer a step.
            with jax.named_scope("kv_dequantize"):
                kb, vb = kb.astype(dtype), vb.astype(dtype)
        scores = jnp.einsum(
            "bskgd,bktd->bkgst", q, kb, preferred_element_type=jnp.float32
        ) / jnp.sqrt(jnp.float32(dk))
        if k_scale is not None:
            # The key's dequantisation, moved past the product (linear in
            # the key).
            with jax.named_scope("kv_dequantize"):
                scores = scores * per_position(k_scale, i)
        col = (i * T + jnp.arange(T))[None, None, :]  # cache position [1, 1, T]
        scores = jnp.where((col <= row)[:, None, None, :, :], scores, lowest)
        new_top = jnp.maximum(top, scores.max(-1))
        weights = jnp.exp(scores - new_top[..., None])
        keep = jnp.exp(top - new_top)
        total = total * keep + weights.sum(-1)
        if v_scale is not None:
            # The value's dequantisation, folded into the weights.
            with jax.named_scope("kv_dequantize"):
                weights = weights * per_position(v_scale, i)
        out = out * keep[..., None] + jnp.einsum(
            "bkgst,bktd->bkgsd", weights.astype(dtype), vb,
            preferred_element_type=jnp.float32,
        )
        return new_top, total, out

    # Position 0 is visible to every query, so the first block leaves every
    # running maximum finite and every sum positive.
    start = (
        jnp.full((B, K, G, S), lowest, jnp.float32),
        jnp.zeros((B, K, G, S), jnp.float32),
        jnp.zeros((B, K, G, S, dv), jnp.float32),
    )
    n = jnp.minimum(blocks_needed(jnp.max(positions) + 1, L), L // T)
    _, total, out = jax.lax.fori_loop(0, n, one_block, start)
    return (out / total[..., None]).astype(dtype).transpose(0, 3, 1, 2, 4)
