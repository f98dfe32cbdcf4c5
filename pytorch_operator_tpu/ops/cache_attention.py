"""Attention of queries against a key/value cache, reading the cache's
filled prefix and not the whole slab.

Every served family keeps a row's keys and values in a slab of
``max_decode_len`` positions (models/llama.py ``Attention._cache_attend``,
the full layers of models/mimo_v2.py, models/nemotron_h.py and
models/phi4_flash.py) of which the traffic fills a part. A query at
position ``p`` needs positions ``[0, p]`` of its row, so the program finds
from ``positions`` how many blocks (:func:`block`) of the slab hold them
and reads no more, in one of two forms that share the block size and the
rounding and nothing else (:func:`reads_per_row` says which a call is):

- **a decode step over a plain slab** (every row of the slabs, a step's
  few queries a row: one, or a verifying step's two, at most
  :data:`STEP_QUERIES`; keys and values in the queries' dtype) reads EACH
  ROW to that row's own depth: a Pallas TPU kernel over a grid of (row,
  block) (:func:`_decode_attention`) whose per-row block counts go in by
  scalar prefetch, a row's queries folded beside the group axis and each
  masked at its own position. A row that holds no request stands at
  position 0 and costs one block; a deep row costs its own blocks and
  nobody else's.
- **every other call** reads to the DEEPEST query's position: a prefill
  chunk (one row, ``slot`` given: its bound is the row's own anyway) and
  an int8 slab with its per-position scales, decode step or chunk. A loop
  with a traced trip count and a running softmax, one compiled body
  whatever is filled. There, rows that hold no request must stand at
  position 0 (serving/engine.py ``decode_block``), or their stale
  positions hold the bound up.

Inside the blocks read the mathematics is the whole slab's in both: the
same dtypes, float32 scores, a float32 softmax (carried as running
maximum, sum and weighted values, normalised once at the end), every
position ``col <= row`` attended, nothing approximated.

The engine counts what the bound saved with the same rounding
(:func:`attended`), on the host, from the positions it already holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

# Blocks a slab is read in: an eighth of the slab, and never more positions
# than :data:`BLOCK_MAX`. Chosen on the chip at 4096 positions (PERF.md
# section 6, PR 29): an iteration costs about 7 us beside its reads, so
# 512 positions a block beat 256 at both families' decode shapes from 512
# filled up, and a static prefix under ``lax.switch`` (30-40 us a
# conditional) up to 2048.
BLOCKS = 8
# The most positions a block holds, whatever the slab's length: a row reads
# whole blocks up to its own depth, so a block that grew with the slab (an
# eighth of 32,768 is 4,096) would charge a row 5,000 deep for 8,192, put a
# ``[K, 4096, d]`` key and value block twice over into fast memory a grid
# step, and make the chunk loop's score block ``[.., chunk, 4096]`` float32.
# At 4,096 positions it is the eighth that every served cell read before
# there was a cap; at 32,768, chosen on the chip among 512 / 1,024 / 2,048
# (PERF.md section 6, PR 46).
BLOCK_MAX = 512

# The most queries a row brings in a decode step: its last accepted token
# and, where the model drafts, the one draft behind it (models/serving.py
# ``Drafter``). Up to here a call over every row is a step, and reads and
# writes (models/layer_list.py ``write_positions``) a row at a time through
# the kernels; anything longer is a chunk.
STEP_QUERIES = 2


def block(L: int) -> int:
    """Positions a block of a slab of ``L`` holds: an eighth of the slab but
    at most :data:`BLOCK_MAX` (where that divides the slab), or all of it
    where eighths do not divide it."""
    if L % BLOCKS:
        return L
    eighth = L // BLOCKS
    return BLOCK_MAX if eighth > BLOCK_MAX and L % BLOCK_MAX == 0 else eighth


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

    A decode step over a plain slab (:func:`reads_per_row`) reads each
    row's blocks up to that row's own deepest position; every other call
    reads only the blocks up to the deepest query's."""
    if reads_per_row(q.shape[1], k_scale is not None, slot is not None):
        return _decode_attention(q, positions, k, v)
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


# ---- a decode step over a plain slab: each row to its own depth ----


def reads_per_row(queries_a_row: int = 1, quantized: bool = False, in_slot: bool = False) -> bool:
    """THE statement of when :func:`cache_attention` reads each row's slab
    to that row's own depth (the kernel below) and when every row's to the
    deepest query's (the loop above). Per row: a call over every row of the
    slabs (no ``slot``) that brings a step's few queries a row (at most
    :data:`STEP_QUERIES`: a decode step's one, a verifying step's two) over
    slabs in the queries' dtype. The loop: an int8 slab (its ``[.., L, 1]``
    scales want a kernel layout of their own) and a prefill chunk (one row
    at ``slot``, whose bound IS the batch's; or any call of more queries a
    row). A model tells the engine which its decode step is by the same
    function (``ServingModel.decode_reads_per_row``), and the engine charges
    ``decode_attended_positions`` what the form it names reads: each row's
    whole blocks up to the row's deepest query."""
    return queries_a_row <= STEP_QUERIES and not quantized and not in_slot


def _decode_attention(q, positions, k, v):
    """:func:`cache_attention` for a decode step (``S`` queries a row, ``S``
    at most :data:`STEP_QUERIES`) over plain slabs, as a Pallas TPU kernel
    over a grid of (row, block): row ``b``'s block count, ``blocks_needed``
    of its deepest query's position + 1, goes in by scalar prefetch and the
    body runs for the blocks below it. A row's ``S`` queries lie folded
    beside the group axis (``[K, S G, dk]``: one product a block for all of
    them) and each is masked at its own position, which goes in by scalar
    prefetch too. The grid's second
    bound is the deepest row's count (found on the device, like the loop's
    trip count), and past a row's own count the index map of the key and
    value blocks names the next row's first block, so a skipped grid step
    fetches nothing and no row waits for its first block. The mathematics
    inside a block is the loop's ``one_block``. On a TPU it is a Mosaic
    kernel; where the default backend is the CPU, the same kernel under
    the interpreter (as ops/flash_attention.py decides). A step's blocks
    ``[K, T, dk]`` + ``[K, T, dv]`` lie in fast memory twice over; ``T`` is
    at most :data:`BLOCK_MAX` positions whatever the slab's length, so what
    the kernel reserves grows with the key heads alone (32 heads of 128 at
    512 positions: 16 MiB)."""
    # Imported here: the library takes about a second, and a process that
    # trains never needs it (one that serves has it from workloads/serve.py's
    # thread).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, K, G, dk = q.shape
    L, dv = k.shape[2], v.shape[-1]
    T = block(L)
    lowest = jnp.finfo(jnp.float32).min
    pos = positions.astype(jnp.int32).reshape(B * S)  # query s of row b at [b * S + s]
    n = jnp.minimum(blocks_needed(jnp.max(positions.astype(jnp.int32), axis=1) + 1, L), L // T)

    def kernel(n_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, top_ref, total_ref, out_ref):
        """One block of one row: ``one_block`` of the loop, on ``q [K, S G,
        dk]`` against ``k [K, T, dk]`` / ``v [K, T, dv]``, the running
        softmax in float32 scratch over the row's grid steps."""
        b, i = pl.program_id(0), pl.program_id(1)

        @pl.when(i == 0)
        def _():
            top_ref[...] = jnp.full_like(top_ref, lowest)
            total_ref[...] = jnp.zeros_like(total_ref)
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(i < n_ref[b])
        def _():
            kb, vb = k_ref[0], v_ref[0]
            scores = jax.lax.dot_general(
                q_ref[0], kb, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
            ) / jnp.sqrt(jnp.float32(dk))  # [K, S G, T]
            col = i * T + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
            # Each query's own position: query s of the row is the folded
            # axis' entries [s G, (s + 1) G).
            at = pos_ref[b * S]
            if S > 1:
                folded = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                for s in range(1, S):
                    at = jnp.where(folded >= s * G, pos_ref[b * S + s], at)
            scores = jnp.where(col <= at, scores, lowest)
            top = top_ref[...]
            new_top = jnp.maximum(top, scores.max(-1, keepdims=True))
            weights = jnp.exp(scores - new_top)
            keep = jnp.exp(top - new_top)
            total_ref[...] = total_ref[...] * keep + weights.sum(-1, keepdims=True)
            out_ref[...] = out_ref[...] * keep + jax.lax.dot_general(
                weights.astype(vb.dtype), vb, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            top_ref[...] = new_top

        @pl.when(i == n_ref[b] - 1)
        def _():
            o_ref[0] = (out_ref[...] / total_ref[...]).astype(o_ref.dtype)

    at_row = lambda b, i, n, pos: (b, 0, 0, 0)  # noqa: E731

    def at_block(b, i, n, pos):
        # Past the row's last needed block the NEXT row's first: its fetch
        # is issued while this row's last block is computed, and the steps
        # that follow find it where it is.
        more = i < n[b]
        return (jnp.where(more, b, jnp.minimum(b + 1, B - 1)), 0, jnp.where(more, i, 0), 0)

    # A grid step's key and value blocks, fetched while the step before
    # computes: beyond the 16 MiB a kernel may use of fast memory unasked
    # (many key heads) the kernel asks for more.
    blocks = 2 * K * T * (dk * k.dtype.itemsize + dv * v.dtype.itemsize)
    # [B, S, K, G, dk] -> [B, K, S G, dk]: a row's queries beside its groups.
    folded = q[:, 0] if S == 1 else q.transpose(0, 2, 1, 3, 4).reshape(B, K, S * G, dk)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, jnp.max(n)),
            in_specs=[
                pl.BlockSpec((1, K, S * G, dk), at_row),
                pl.BlockSpec((1, K, T, dk), at_block),
                pl.BlockSpec((1, K, T, dv), at_block),
            ],
            out_specs=pl.BlockSpec((1, K, S * G, dv), at_row),
            scratch_shapes=[
                pltpu.VMEM((K, S * G, 1), jnp.float32),
                pltpu.VMEM((K, S * G, 1), jnp.float32),
                pltpu.VMEM((K, S * G, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, S * G, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=max(16 << 20, blocks + (4 << 20))
        ),
        interpret=jax.default_backend() == "cpu",
        name="cache_attention_decode",
    )(n, pos, folded, k, v)
    return out[:, None] if S == 1 else out.reshape(B, K, S, G, dv).transpose(0, 2, 1, 3, 4)
