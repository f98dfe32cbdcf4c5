"""Ulysses-style sequence parallelism — all-to-all head/sequence swap.

The second of the two standard long-context parallelism schemes (the
reference has neither — SURVEY.md §5 "Long-context / sequence
parallelism": absent). Complementary to ring attention
(``parallel/ring.py``):

- **ring**: K/V shards rotate P times over ICI neighbor links; scores
  stay blockwise O(S/P x S/P) — minimal memory, P communication steps
  that must each hide behind a block of attention math.
- **ulysses** (this module, after DeepSpeed-Ulysses): ONE all-to-all
  re-shards activations from sequence-sharded to head-sharded, attention
  runs with the FULL sequence but 1/P of the kv-heads per device, and a
  second all-to-all swaps back. Two collectives total regardless of P
  (all-to-all is cheap on a TPU torus), at the price of full-S score
  blocks per local head — the right trade when heads are plentiful and
  S is moderate; ring wins when S is extreme.

Layout matches ``models/llama.py`` grouped-query attention (q
``[B,S,K,G,D]``, k/v ``[B,S,K,D]``, positions ``[B,S]``); requires
``n_kv_heads % sp == 0`` (heads are the resharding currency). Exposed in
the flagship model as ``attn_impl="ulysses"``.
"""

from __future__ import annotations


def _attend_full_seq(q, k, v, positions, *, causal: bool):
    """Dense softmax attention over the full sequence for the LOCAL head
    subset (heads are embarrassingly parallel, so per-device numerics are
    identical to the unsharded computation). Shares ring.py's oracle so
    the two sp schemes cannot drift numerically."""
    from .ring import _single_shard

    return _single_shard(q, k, v, positions, causal=causal)


def ulysses_attention_shard(
    q,
    k,
    v,
    positions_full,
    *,
    axis_name: str = "sp",
    causal: bool = True,
):
    """Per-shard body (usable under any manual ``shard_map``): q
    ``[B,S/P,K,G,D]``, k/v ``[B,S/P,K,D]`` sequence-sharded;
    ``positions_full`` ``[B,S]`` (every device needs the global positions
    for the causal mask). Returns ``[B,S/P,K,G,D]``."""
    import jax

    # seq-sharded -> head-sharded: split the kv-head axis P ways, gather
    # the sequence axis. tiled=True keeps plain array semantics.
    qh = jax.lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = jax.lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = jax.lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    out = _attend_full_seq(qh, kh, vh, positions_full, causal=causal)
    # head-sharded -> seq-sharded (the inverse swap).
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_self_attention(
    q,
    k,
    v,
    positions,
    mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
):
    """Global-view Ulysses attention: seq dim sharded over ``axis_name``.

    Mirrors ``ring_self_attention``'s contract: global arrays in/out,
    shard_map manual over ``axis_name`` ONLY (batch/head dims stay
    compiler-managed so dp/fsdp/tp sharding composes). Falls back to the
    single-shard path when the axis is absent/size-1 or the RUNTIME
    shape doesn't divide (S % P) — same one-code-path promise as ring's
    degenerate handling. A kv-head count that doesn't divide the sp
    extent is a STATIC config error and raises: silently running dense
    full-S attention at the long contexts ulysses exists for would lose
    the entire memory/perf win while the operator believes sp is active.
    """
    import functools

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .ring import _single_shard

    n = mesh.shape.get(axis_name, 1) if axis_name in mesh.axis_names else 1
    if n > 1 and q.shape[2] % n:
        raise ValueError(
            f"attn_impl='ulysses' needs n_kv_heads % {axis_name} == 0 "
            f"(kv heads are the resharding currency): got "
            f"{q.shape[2]} kv heads, {axis_name}={n}. Use a config with "
            f"divisible kv heads, a smaller {axis_name}, or attn_impl='ring'."
        )
    if n == 1 or q.shape[1] % n:
        return _single_shard(q, k, v, positions, causal=causal)

    body = functools.partial(
        ulysses_attention_shard, axis_name=axis_name, causal=causal
    )
    return shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None, None),
            P(None, axis_name, None, None),
            P(None, axis_name, None, None),
            P(),  # positions replicated: the mask needs the global view
        ),
        out_specs=P(None, axis_name, None, None, None),
        axis_names={axis_name},
    )(q, k, v, positions)
