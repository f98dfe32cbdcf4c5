"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference has no long-context machinery (SURVEY.md §5 "Long-context /
sequence parallelism": absent); this is the TPU-native capability the
rebuild adds so sequences longer than one chip's HBM can be trained: shard
the sequence over ``sp``, keep Q local, and rotate K/V shards around the
ring with ``jax.lax.ppermute`` while accumulating attention in the
streaming (online-softmax / flash) form. Peak memory per chip is
O(S/sp · S/sp) for scores instead of O(S · S), and the ppermute rides ICI
neighbor links — the cheapest collective a TPU torus has.

Layout matches ``models/llama.py`` grouped-query attention:

- q: ``[B, S, K, G, D]`` (K kv-heads × G query groups)
- k, v: ``[B, S, K, D]``
- positions: ``[B, S]`` global token positions (drive the causal mask, so
  shards need no index arithmetic — masking keys on ``k_pos <= q_pos`` is
  correct regardless of which shard a block came from).

``ring_attention_shard`` is the per-shard body (usable under any manual
``shard_map``); ``ring_self_attention`` is the user-facing wrapper that
applies ``shard_map`` manual over ``sp`` only, leaving batch/head axes to
the compiler (partial-manual ``axis_names={'sp'}``).
"""

from __future__ import annotations

import functools


def ring_attention_shard(
    q,
    k,
    v,
    q_positions,
    kv_positions,
    *,
    axis_name: str = "sp",
    causal: bool = True,
):
    """Streaming attention over K/V shards rotated around ``axis_name``.

    Shapes (per shard): q ``[B,Sq,K,G,D]``, k/v ``[B,Skv,K,D]``,
    q_positions ``[B,Sq]``, kv_positions ``[B,Skv]``. Returns
    ``[B,Sq,K,G,D]`` in q's dtype.

    Accumulation is float32 online softmax: running max ``m``, denominator
    ``l``, numerator ``o``; each incoming K/V block rescales the
    accumulators by ``exp(m - m_new)``. Fully-masked blocks contribute
    exactly zero (their ``exp(scores - m_new)`` underflows to 0 against the
    finite mask value), and causal masking guarantees every query row sees
    at least its own diagonal in the step-0 (local) block, so ``m`` is
    finite from the first step and no NaN guards are needed.
    """
    import jax
    import jax.numpy as jnp

    B, Sq, K, G, D = q.shape
    n = jax.lax.axis_size(axis_name)
    scale = 1.0 / (D**0.5)
    neg = jnp.finfo(jnp.float32).min

    q32 = q.astype(jnp.float32) * scale

    def block(carry, kv_block):
        m, l, o = carry
        k_blk, v_blk, kv_pos = kv_block
        # [B,K,G,Sq,Skv] scores in f32 (MXU-friendly contraction).
        s = jnp.einsum(
            "bskgd,btkd->bkgst", q32, k_blk, preferred_element_type=jnp.float32
        )
        if causal:
            ok = kv_pos[:, None, None, None, :] <= q_positions[:, None, None, :, None]
            s = jnp.where(ok, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)  # [B,K,G,Sq]
        p = jnp.exp(s - m_new[..., None])  # [B,K,G,Sq,Skv]
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, v_blk, preferred_element_type=jnp.float32
        )
        return m_new, l, o

    # Remat the block: without it, grad through the ring loop saves every
    # step's [B,K,G,Sq,Skv] softmax intermediates as scan residuals —
    # O(Sq_local * S_total) per chip, the exact quadratic blowup this
    # module exists to avoid. Recomputing p in backward keeps residuals
    # at the carry + the rotated K/V blocks (linear in S).
    block = jax.checkpoint(block)

    # Accumulators start as (replicated) constants but become device-varying
    # after the first block; mark them varying over the ring axis up front so
    # the fori_loop carry type is stable (shard_map VMA typing).
    def varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m0 = varying(jnp.full((B, K, G, Sq), neg, jnp.float32))
    l0 = varying(jnp.zeros((B, K, G, Sq), jnp.float32))
    o0 = varying(jnp.zeros((B, K, G, Sq, D), jnp.float32))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(i, carry):
        del i
        (m, l, o), (k_cur, v_cur, pos_cur) = carry
        m, l, o = block((m, l, o), (k_cur, v_cur, pos_cur))
        # Rotate K/V (and their positions) one hop around the ring. The
        # final rotation is redundant work but keeps the loop body uniform
        # (and XLA overlaps the ppermute with the block math above).
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        pos_nxt = jax.lax.ppermute(pos_cur, axis_name, perm)
        return (m, l, o), (k_nxt, v_nxt, pos_nxt)

    # K/V rotate in their input dtype (bf16 in production) — halving ppermute
    # bytes over ICI; the einsums' preferred_element_type gives f32 accumulate.
    (m, l, o), _ = jax.lax.fori_loop(
        0, n, step, ((m0, l0, o0), (k, v, kv_positions))
    )
    # [B,K,G,Sq,D] → [B,Sq,K,G,D]; l is > 0 (causal diagonal) everywhere.
    out = o / l[..., None]
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)


def ring_self_attention(
    q,
    k,
    v,
    positions,
    mesh,
    *,
    axis_name: str = "sp",
    causal: bool = True,
):
    """Global-view ring attention: shard the seq dim over ``axis_name``.

    q ``[B,S,K,G,D]``, k/v ``[B,S,K,D]``, positions ``[B,S]`` are global
    arrays (typically already seq-sharded by pjit); shard_map is manual over
    ``axis_name`` ONLY — batch and head dims stay compiler-managed so dp /
    fsdp / tp sharding composes without re-specifying it here.
    """
    import jax

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if (
        axis_name not in mesh.axis_names
        or mesh.shape[axis_name] == 1
        # A sequence that doesn't divide the ring cannot be sharded —
        # fall back to the single-shard path instead of a trace-time
        # shard_map error (same one-code-path promise as the degenerate
        # mesh case).
        or q.shape[1] % mesh.shape[axis_name]
    ):
        return _single_shard(q, k, v, positions, causal=causal)
    body = functools.partial(
        ring_attention_shard, axis_name=axis_name, causal=causal
    )
    return shard_map(
        lambda q, k, v, p: body(q, k, v, p, p),
        mesh=mesh,
        in_specs=(
            P(None, axis_name, None, None, None),
            P(None, axis_name, None, None),
            P(None, axis_name, None, None),
            P(None, axis_name),
        ),
        out_specs=P(None, axis_name, None, None, None),
        axis_names={axis_name},
    )(q, k, v, positions)


def _single_shard(q, k, v, positions, *, causal: bool):
    """Reference (non-ring) streaming attention on one shard — also the
    numerics oracle the ring path is tested against."""
    import jax.numpy as jnp

    D = q.shape[-1]
    s = jnp.einsum(
        "bskgd,btkd->bkgst",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) / (D**0.5)
    if causal:
        ok = positions[:, None, None, None, :] <= positions[:, None, None, :, None]
        s = jnp.where(ok, s, jnp.finfo(jnp.float32).min)
    import jax

    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkgst,btkd->bskgd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return out.astype(q.dtype)
