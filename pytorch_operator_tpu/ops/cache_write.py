"""A decode step's write into a key/value cache: one new position a row a
call (a verifying step's two positions are two calls:
models/layer_list.py ``write_positions``).

Every layer-list family (models/mimo_v2.py, models/nemotron_h.py,
models/phi4_flash.py) keeps a layer's keys and values as leaves ``[rows,
key heads, positions, head size]``: a slab of ``max_decode_len`` positions,
or a window layer's ring. A decode step puts ONE position into each row, each
row at a place of its own, so the update is a scatter over the rows; left to
the compiler it is a loop of one trip a row on the core's scalar unit
(~3.9 us a trip whatever the bytes: PERF.md section 6, PR 40), which at 64 to
128 rows and up to 27 leaves a step was more than the walks that read them.

:func:`write_rows` is that scatter as one Pallas TPU kernel over a grid of
rows: row ``b``'s place ``idx[b]`` goes in by scalar prefetch, the index map
of the leaf's block names the aligned tile of :data:`TILE` positions that
holds it, the body puts the new position into the tile, and the leaf is
aliased to the result, so nothing but a row's one tile moves and the
pipeline fetches the next row's tile while this row's is written (no two
rows share a tile, so updating in place has no hazard). A tile and not the
position alone: rows of a bfloat16 leaf lie two to a 32-bit sublane, and
the chip's compiler refuses a copy of half of one. Several leaves that share
rows and positions (a layer's keys and values) go through ONE call.

On a TPU it is a Mosaic kernel; where the default backend is the CPU, the
same kernel under the interpreter (as ops/cache_attention.py decides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Positions a tile holds: the sublanes of a bfloat16 tile (two of a float32
# one), so a block is whole tiles of either.
TILE = 16


def tile(T: int) -> int:
    """Positions of the block a row's write moves: :data:`TILE`, or the whole
    axis where tiles do not divide it (the tiny caches of the CPU tests)."""
    return TILE if T % TILE == 0 else T


def write_rows(slabs, vals, idx):
    """``slabs`` (a tuple of leaves ``[B, Hk, T, d]`` with ``B`` and ``T`` in
    common) with ``vals`` (``[B, Hk, 1, d]`` each, in its leaf's dtype)
    written at position ``idx [B]`` (int32, in ``[0, T)``) of each row.
    Every other position keeps its bits: it is not computed with, only the
    row's own tile is rewritten as it was read. Returns the new leaves; the
    old ones' buffers are reused where the caller lets them go (a donated
    cache)."""
    # Imported here: the library takes about a second, and a process that
    # serves an int8 cache or trains never needs it.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(slabs)
    B, _, T, _ = slabs[0].shape
    t = tile(T)

    def kernel(idx_ref, *refs):
        """One row: each leaf's tile ``[Hk, t, d]`` with the new position
        ``[Hk, 1, d]`` in its place."""
        at = idx_ref[pl.program_id(0)] % t
        for new, old, out in zip(refs[:n], refs[n : 2 * n], refs[2 * n :]):
            row = jax.lax.broadcasted_iota(jnp.int32, old.shape[1:], 1)
            out[0] = jnp.where(row == at, new[0], old[0])

    at_row = lambda b, idx: (b, 0, 0, 0)  # noqa: E731
    at_tile = lambda b, idx: (b, 0, idx[b] // t, 0)  # noqa: E731
    tiles = [pl.BlockSpec((1, s.shape[1], t, s.shape[3]), at_tile) for s in slabs]
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, s.shape[1], 1, s.shape[3]), at_row) for s in slabs] + tiles,
            out_specs=tiles,
        ),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in slabs],
        # Operand 0 is the prefetched ``idx``; the leaves follow the values.
        input_output_aliases={1 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() == "cpu",
        name="cache_write_rows",
    )(idx.astype(jnp.int32), *vals, *slabs)
