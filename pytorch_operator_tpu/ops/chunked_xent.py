"""Memory-efficient softmax cross-entropy for large-vocab LM heads.

The dense path materializes ``[N, V]`` float32 logits (plus their gradient):
for Llama-3-8B shapes (V=128256) that is ~1 GB per 2048 tokens and it is
pure HBM traffic. This op fuses the LM-head matmul with the loss: a
``lax.scan`` over vocab chunks keeps only ``[N, chunk]`` live, carrying the
online logsumexp (running max + scaled sum — the same trick flash attention
uses along the key axis, applied to the vocab axis), and the backward pass
recomputes each chunk's logits instead of saving them.

Weight access is by ``lax.dynamic_slice_in_dim`` along the vocab axis — no
reshape/transpose relayout of the full ``[D, V]`` weight is ever created.
A vocab that does not divide into chunks is handled by clamped tail slices
with already-counted columns masked out (no padding copy either).

Reference parity note: nothing like this exists in the reference (its loss
is whatever the user container does); this is a beyond-parity TPU
optimization for the BASELINE.json:10 Llama workload.

HBM cost per step: O(N*chunk) activations instead of O(N*V); the weight
gradient is still [D, V] (it is a parameter gradient, unavoidable).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def chunked_softmax_xent(hidden, w, labels, *, chunk: int = 8192):
    """Per-token ``-log p(label)`` without materializing ``[N, V]`` logits.

    hidden ``[N, D]`` (bf16/f32), w ``[D, V]`` (the lm_head kernel),
    labels ``[N]`` int. Returns float32 ``[N]``. Gradients flow to
    ``hidden`` and ``w``; logits math is float32 regardless of input dtype
    (matching the dense path, whose head computes in f32).

    Out-of-range labels clamp to [0, V) — a DEFINED behavior where the
    dense path (optax integer-label xent) yields NaN and the previous
    chunked behavior silently returned plain lse. Padding/ignore tokens
    should be masked out of the mean, not encoded as sentinel label ids;
    the clamp just guarantees a stray id can't poison the loss.
    """
    N, D = hidden.shape
    D2, V = w.shape
    assert D == D2, f"hidden D={D} vs w D={D2}"
    c = min(chunk, V)
    n_chunks = -(-V // c)  # ceil — tail chunk is a clamped, masked slice
    labels = jnp.clip(labels.astype(jnp.int32), 0, V - 1)
    return _xent(hidden, w, labels, n_chunks, c)


def chunked_vocab_stats(hidden, w, labels, *, chunk: int = 8192, col_offset=0):
    """Online softmax partial stats of ``hidden @ w`` for a (possibly
    vocab-sharded) head chunk — the combinable form of
    :func:`chunked_softmax_xent` for the pipeline's vocab-parallel loss
    tail (models/llama.py train_value_and_grad_pp). Returns f32 ``[N]``
    triples:

    - ``m``: max logit over THIS weight's columns (stop-gradient — the
      shift is numerics-only);
    - ``s``: sum of ``exp(logit - m)``;
    - ``lab_logit``: the label's logit where the GLOBAL label id falls in
      ``[col_offset, col_offset + w.shape[1])``, else 0.

    Owners combine across shards with one pmax + two psums:
    ``M = pmax(m); lse = M + log(psum(s * exp(m - M))); loss = lse -
    psum(lab_logit)``. Plain autodiff (no custom VJP): each sub-chunk
    body is ``jax.checkpoint``'d, so backward recomputes its ``[N,
    chunk]`` logits instead of saving one residual buffer per chunk —
    same peak-memory contract as chunked_softmax_xent. Pass
    ``chunk >= w.shape[1]`` for a single dense pass over the local
    columns.
    """
    N, D = hidden.shape
    D2, Vl = w.shape
    assert D == D2, f"hidden D={D} vs w D={D2}"
    c = min(chunk, Vl)
    n_chunks = -(-Vl // c)
    hidden32 = hidden.astype(jnp.float32)
    labels = labels.astype(jnp.int32) - col_offset  # local column ids

    def body(carry, c_idx):
        m, s, lab_logit = carry
        w_c, start = _chunk_slice(w, c_idx, c)
        logits = hidden32 @ w_c.astype(jnp.float32)  # [N, c] f32
        logits = jnp.where(
            _fresh_mask(start, c_idx, c)[None, :], logits, -jnp.inf
        )
        m_new = jnp.maximum(
            m, jax.lax.stop_gradient(logits.max(axis=-1))
        )
        s = s * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(
            axis=-1
        )
        local = labels - start
        in_chunk = (labels >= c_idx * c) & (local < c) & (local >= 0)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, c - 1)[:, None], axis=-1
        )[:, 0]
        lab_logit = jnp.where(in_chunk, picked, lab_logit)
        return (m_new, s, lab_logit), None

    if n_chunks > 1:
        body = jax.checkpoint(body)
    init = _match_vma(
        (
            jnp.full((N,), -jnp.inf, jnp.float32),
            jnp.zeros((N,), jnp.float32),
            jnp.zeros((N,), jnp.float32),
        ),
        hidden,
    )
    (m, s, lab_logit), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return m, s, lab_logit


def _match_vma(tree, ref):
    """pcast every leaf of ``tree`` to carry ``ref``'s varying manual
    axes (shard_map vma) — makes freshly-built scan carries type-stable
    when this op runs inside a manual region. Identity elsewhere."""
    vma = jax.typeof(ref).vma
    if not vma:
        return tree
    return jax.tree.map(
        lambda v: (
            v
            if jax.typeof(v).vma >= vma
            else jax.lax.pcast(v, tuple(vma), to="varying")
        ),
        tree,
    )


def _chunk_slice(w, c_idx, chunk):
    """``w[:, start : start+chunk]`` with the clamped start dynamic_slice
    uses; returns (w_chunk, start). For the tail chunk start < c_idx*chunk,
    so some columns repeat — callers mask them (global col < c_idx*chunk)."""
    V = w.shape[1]
    start = jnp.minimum(c_idx * chunk, V - chunk)
    return jax.lax.dynamic_slice_in_dim(w, start, chunk, axis=1), start


def _fresh_mask(start, c_idx, chunk):
    """True for columns not already counted by earlier chunks."""
    global_col = start + jnp.arange(chunk)
    return global_col >= c_idx * chunk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _xent(hidden, w, labels, n_chunks: int, chunk: int):
    loss, _ = _xent_fwd(hidden, w, labels, n_chunks, chunk)
    return loss


def _xent_fwd(hidden, w, labels, n_chunks: int, chunk: int):
    N, D = hidden.shape
    hidden32 = hidden.astype(jnp.float32)

    def body(carry, c_idx):
        m, s, lab_logit = carry
        w_c, start = _chunk_slice(w, c_idx, chunk)
        logits = hidden32 @ w_c.astype(jnp.float32)  # [N, chunk] f32
        logits = jnp.where(
            _fresh_mask(start, c_idx, chunk)[None, :], logits, -jnp.inf
        )
        m_new = jnp.maximum(m, logits.max(axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.exp(logits - m_new[:, None]).sum(axis=-1)
        local = labels - start
        in_chunk = (labels >= c_idx * chunk) & (local < chunk)
        picked = jnp.take_along_axis(
            logits, jnp.clip(local, 0, chunk - 1)[:, None], axis=-1
        )[:, 0]
        lab_logit = jnp.where(in_chunk, picked, lab_logit)
        return (m_new, s, lab_logit), None

    init = (
        jnp.full((N,), -jnp.inf, jnp.float32),
        jnp.zeros((N,), jnp.float32),
        jnp.zeros((N,), jnp.float32),
    )
    # Inside a shard_map manual region (the 1F1B pipeline's loss tail)
    # the scan body is axis-varying via hidden/w while these fresh zeros
    # are invariant — pcast so the carry types agree. No-op outside
    # manual regions (vma is empty there).
    init = _match_vma(init, hidden)
    (m, s, lab_logit), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    lse = m + jnp.log(s)
    return lse - lab_logit, (hidden, w, labels, lse)


def _xent_bwd(n_chunks: int, chunk: int, res, ct):
    """Recompute each chunk's logits; accumulate dW in place via
    dynamic_update_slice (read-add-write on a [D, V] carry), dH via matmul."""
    hidden, w, labels, lse = res
    N, D = hidden.shape
    hidden32 = hidden.astype(jnp.float32)
    ct32 = ct.astype(jnp.float32)

    def body(carry, c_idx):
        dh, dw = carry
        w_c, start = _chunk_slice(w, c_idx, chunk)
        w_c32 = w_c.astype(jnp.float32)
        p = jnp.exp(hidden32 @ w_c32 - lse[:, None])  # softmax chunk
        local = labels - start
        in_chunk = (labels >= c_idx * chunk) & (local < chunk)
        g = p * ct32[:, None]  # [N, chunk]
        # Label correction as a scatter-add, NOT a materialized one-hot —
        # a second [N, chunk] buffer here is what blows peak HBM at the
        # batch sizes this op exists for.
        g = g.at[jnp.arange(g.shape[0]), jnp.clip(local, 0, chunk - 1)].add(
            -ct32 * in_chunk,
            # One update per row, rows ascending: let XLA skip the
            # collision-safe scatter lowering.
            unique_indices=True,
            indices_are_sorted=True,
        )
        # Tail chunk: zero the already-counted columns so the overlapped
        # read-add-write below cannot double-contribute.
        g = g * _fresh_mask(start, c_idx, chunk)[None, :]
        dh = dh + g @ w_c32.T
        dw_c = jax.lax.dynamic_slice_in_dim(dw, start, chunk, axis=1)
        dw = jax.lax.dynamic_update_slice_in_dim(
            dw, dw_c + hidden32.T @ g, start, axis=1
        )
        return (dh, dw), None

    (dh, dw), _ = jax.lax.scan(
        body,
        _match_vma(
            (jnp.zeros((N, D), jnp.float32), jnp.zeros(w.shape, jnp.float32)),
            hidden,
        ),
        jnp.arange(n_chunks),
    )
    zeros_lab = np.zeros(labels.shape, jax.dtypes.float0)
    return dh.astype(hidden.dtype), dw.astype(w.dtype), zeros_lab


_xent.defvjp(_xent_fwd, _xent_bwd)
