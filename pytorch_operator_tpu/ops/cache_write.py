"""A decode step's write into a key/value cache: one new position a row a
call (a verifying step's two positions are two calls:
models/layer_list.py ``write_positions``).

Every served family keeps a layer's keys and values as leaves ``[rows, key
heads, positions, head size]``: a slab of ``max_decode_len`` positions, or a
window layer's ring; the llama family's int8 cache (models/llama.py) keeps
int8 keys and values with a float32 scale a position and head beside each,
``[rows, key heads, positions, 1]``. A decode step puts ONE position into
each row, each row at a place of its own, so the update is a scatter over
the rows; left to the compiler it is a loop of one trip a row on the core's
scalar unit (3.1–3.9 us a trip whatever the bytes: PERF.md section 6, PR 40),
which at 8 to 128 rows and up to 96 leaves a step was more than the walks
that read them.

:func:`write_rows` is that scatter as one Pallas TPU kernel over a grid of
rows: row ``b``'s place ``idx[b]`` goes in by scalar prefetch, the index map
of the leaf's block names the aligned tile of positions that holds it, the
body puts the new position into the tile, and the leaf is aliased to the
result, so nothing but a row's one tile moves and the pipeline fetches the
next row's tile while this row's is written (no two rows share a tile, so
updating in place has no hazard). A tile and not the position alone: rows of
a bfloat16 leaf lie two to a 32-bit sublane (of an int8 leaf four), and the
chip's compiler refuses a copy of part of one. Several leaves that share
rows and positions (a layer's keys and values, an int8 layer's scales too)
go through ONE call. What differs from leaf to leaf, the tile and how a
scale leaf is seen, is read off the leaf's dtype and shape (:func:`_blocked`).

On a TPU it is a Mosaic kernel; where the default backend is the CPU, the
same kernel under the interpreter (as ops/cache_attention.py decides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

# Positions a tile holds: the sublanes of a bfloat16 tile (two of a float32
# one), so a block is whole tiles of either. An int8 leaf's tile holds 32.
TILE = 16
# A scale leaf ``[B, Hk, T, 1]`` lies position-minor on the device: seen as
# rows of LANES positions the same bytes are tiles of SCALE_ROWS such rows.
LANES, SCALE_ROWS = 128, 8
# ... if the compiler keeps it so: left free, it carried the scale leaves
# through a decode dispatch's loop heads-on-sublanes, where the view below is
# a copy of the whole leaf in and out of every call.
POSITION_MINOR = Layout(major_to_minor=(0, 1, 3, 2))


def tile(T: int, dtype=jnp.bfloat16) -> int:
    """Positions of the block a row's write moves: the leaf's own tile
    (:data:`TILE`; four positions share a sublane's 32 bits where the leaf is
    int8, so 32), or the whole axis where tiles do not divide it (the tiny
    caches of the CPU tests)."""
    t = max(TILE, 32 // jnp.dtype(dtype).itemsize)
    return t if T % t == 0 else T


def _blocked(slab):
    """A leaf as the kernel sees it, ``[B, Hk, rows, lanes]``, with the rows
    of its block and the positions a row holds: position ``p`` is at row ``p
    // per``, lane(s) ``p % per``. A leaf of keys or values is itself, a row a
    position, a block its tile. A scale leaf (last axis 1) handed over as it
    is, or squeezed to ``[B, Hk, T]``, would be copied whole into a
    heads-on-sublanes layout and back every call; as ``[B, Hk, T // LANES,
    LANES]`` the same bytes are a bitcast, a block SCALE_ROWS rows of it; where
    whole blocks do not divide ``T`` it is the one row ``[1, T]``."""
    B, Hk, T, d = slab.shape
    if d != 1:
        return slab, tile(T, slab.dtype), 1
    slab = with_layout_constraint(slab, POSITION_MINOR)
    if T % (SCALE_ROWS * LANES):
        return slab.reshape(B, Hk, 1, T), 1, T
    return slab.reshape(B, Hk, T // LANES, LANES), SCALE_ROWS, LANES


def write_rows(slabs, vals, idx):
    """``slabs`` (a tuple of leaves ``[B, Hk, T, d]`` with ``B`` and ``T`` in
    common: keys and values, and an int8 cache's float32 scales ``[B, Hk, T,
    1]`` beside them) with ``vals`` (``[B, Hk, 1, d]`` each, in its leaf's
    dtype) written at position ``idx [B]`` (int32, in ``[0, T)``) of each row.
    Every other position keeps its bits: it is not computed with, only the
    row's own tile is rewritten as it was read. Returns the new leaves; the
    old ones' buffers are reused where the caller lets them go (a donated
    cache)."""
    # Imported here: the library takes about a second, and a process that
    # trains never needs it (one that serves imports it on a thread beside
    # the backend's start: workloads/serve.py).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(slabs)
    B = slabs[0].shape[0]
    seen, rows, per = zip(*map(_blocked, slabs))

    def kernel(idx_ref, *refs):
        """One row: each leaf's block ``[Hk, rows, lanes]`` with the new
        position (``[Hk, 1, d]``; a scale's one number a head) in its place."""
        at = idx_ref[pl.program_id(0)]
        news, olds, outs = refs[:n], refs[n : 2 * n], refs[2 * n :]
        for r, p, new, old, out in zip(rows, per, news, olds, outs):
            row = jax.lax.broadcasted_iota(jnp.int32, old.shape[1:], 1)
            if p == 1:
                here = row == at % r
            else:
                lane = jax.lax.broadcasted_iota(jnp.int32, old.shape[1:], 2)
                here = (row == at // p % r) & (lane == at % p)
            out[0] = jnp.where(here, new[0], old[0])

    at_row = lambda b, idx: (b, 0, 0, 0)  # noqa: E731

    def block(s, r, p):
        """Leaf ``s``'s block: the ``r`` rows, ``r * p`` positions, that hold
        a row's place."""
        at_place = lambda b, idx: (b, 0, idx[b] // (r * p), 0)  # noqa: E731
        return pl.BlockSpec((1, s.shape[1], r, s.shape[3]), at_place)

    blocks = [block(s, r, p) for s, r, p in zip(seen, rows, per)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, *v.shape[1:]), at_row) for v in vals] + blocks,
            out_specs=blocks,
        ),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype) for s in seen],
        # Operand 0 is the prefetched ``idx``; the leaves follow the values.
        input_output_aliases={1 + n + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=jax.default_backend() == "cpu",
        name="cache_write_rows",
    )(idx.astype(jnp.int32), *vals, *seen)
    # A scale leaf back as the cache holds it, the same bytes again.
    return [
        o if o.shape == s.shape else with_layout_constraint(o.reshape(s.shape), POSITION_MINOR)
        for o, s in zip(out, slabs)
    ]
