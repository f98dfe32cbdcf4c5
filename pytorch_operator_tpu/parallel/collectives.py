"""Collective wrappers for use inside ``shard_map`` — the XLA-over-ICI/DCN
replacement for the NCCL/Gloo layer the reference delegated to user
containers (SURVEY.md §5 "Distributed communication backend").

These are thin, named wrappers so workloads read like the topology they
implement (ring_shift for ring attention, reduce-scatter for ZeRO grads...).
"""

from __future__ import annotations

from typing import Any


def psum(x: Any, axis: str):
    import jax

    return jax.lax.psum(x, axis)


def pmean(x: Any, axis: str):
    import jax

    return jax.lax.pmean(x, axis)


def all_gather(x: Any, axis: str, *, tiled: bool = True):
    import jax

    return jax.lax.all_gather(x, axis, tiled=tiled)


def reduce_scatter(x: Any, axis: str, *, scatter_dimension: int = 0):
    import jax

    return jax.lax.psum_scatter(
        x, axis, scatter_dimension=scatter_dimension, tiled=True
    )


def ring_shift(x: Any, axis: str, *, shift: int = 1):
    """Cyclic shift along a mesh axis via ppermute — the building block of
    ring attention and the smoke-dist ring canary. shift=+1 sends each
    shard to the next rank (rank i's output = rank i-1's input)."""
    import jax

    n = jax.lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    import jax

    return jax.lax.axis_index(axis)


def axis_size(axis: str):
    import jax

    return jax.lax.axis_size(axis)
