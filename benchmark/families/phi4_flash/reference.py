"""The plain reference of the ``phi4_flash`` family: the decoder-hybrid-decoder
(arXiv:2507.06607) in straightforward float32 ``jax.numpy``. No cache, no
batching, no chunks, no kernels, no skipped layers: one sequence, EVERY layer
on EVERY token, the state-space scan as the sequential recurrence it is
defined by, attention as whole score matrices under a mask.

The equations (ISSUE 36 A; each departure from the published code is listed
in the configuration file's ``assumed``). Layer ``l``: ``h = x + mixer_l(LN
(x))``, ``x' = h + W2 (u * silu(g))`` with ``[g | u] = LN'(h) W1``; LN is
LayerNorm with scale and bias; after the last layer a final LN and ``logits =
x E^T`` with the embedding ``E``. No position embedding anywhere. The mixer
by the layer's kind (``shape.layer_kinds``):

- Mamba-1 (even ``l <= n/2``): ``[u | z] = x W_in``; ``c_t = b + sum_j w_j
  u_{t-K+1+j}`` (zeros before the sequence), ``u <- silu(c)``; ``[delta | B |
  C] = u W_x``; ``dt_t = softplus(delta_t W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``S_t[c, n] = exp(dt_t[c] A[c, n]) S_{t-1}[c, n] + dt_t[c] B_t[n] u_t[c]``
  from ``S_{-1} = 0``; ``y_t[c] = sum_n S_t[c, n] C_t[n] + D[c] u_t[c]``;
  ``out = (y * silu(z)) W_out``. Layer ``n/2`` also hands on ``m_t = y_t``.
- differential attention with its own keys and values (odd ``l < n/2``: a
  window; ``l = n/2 + 1``: full): ``q = x W_q + b_q``, ``[k | v] = x W_kv +
  b_kv``; query heads ``(2i, 2i + 1)`` are the pair ``(q1_i, q2_i)``, key
  heads ``(2g, 2g + 1)`` the pair ``(k1_g, k2_g)``, ``v_g`` value heads ``2g``
  and ``2g + 1`` side by side; pair ``i`` uses ``g = i // (H / Hk)``; ``P1 =
  softmax_t(q1_i . k1_g,t / sqrt(dh))``, ``P2`` alike, over the visible ``t``
  (``t <= p``, and ``p - t < window`` for a window layer); ``o_i = sum_t
  (P1_t - lam P2_t) v_g,t``; ``o_i <- (1 - lam_init) gamma * o_i / rms(o_i)``
  (over the ``2 dh``); ``out = o W_o + b_o``; ``lam = exp(lq1 . lk1) -
  exp(lq2 . lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``.
- cross-attention (odd ``l > n/2 + 1``): ``q`` alone, against layer ``n/2 +
  1``'s keys and values at every ``t <= p``; its own ``lam``, ``gamma``,
  ``W_o``, ``b_o``.
- gated memory unit (even ``l > n/2``): ``out = (silu(x W_g) * m_t) W_o``.

**Precision.** Everything is float32 at ``jax.default_matmul_precision
("highest")``; ``rnd`` (the identity) is applied to both operands of every
matrix product, which is where the control, the next precision down, puts
float8 (e4m3) in. The weights are the configuration's: matrices made in
bfloat16 and widened. Nothing routes, so EVERY served position is compared.

``serve_check`` is what ``reference_run.py`` calls (the contract is stated
there). ``train_check`` raises: this family is served, not trained.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.families._common import fp8_round, highest, identity

from . import weights as W
from .shape import CROSS, FULL, GMU, MAMBA, MAMBA_MEMORY, WINDOW


def layer_norm(x, w, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w["scale"] + w["bias"]


# ---- the mixers ----


def mamba(x, w, d, rnd=identity):
    """x [S, D] -> (out [S, D], y [S, di]): the sequential recurrence from a
    zero state; ``y`` is the scan's output with the ``D`` term, before the gate."""
    S = x.shape[0]
    di, N, K, R = d["di"], d["N"], d["K"], d["R"]
    proj = rnd(x) @ rnd(w["in_proj"])
    u, z = proj[:, :di], proj[:, di:]
    window = jnp.concatenate([jnp.zeros((K - 1, di), jnp.float32), u])
    u = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * window[j : j + S] for j in range(K)))
    low = rnd(u) @ rnd(w["x_proj"])
    delta, Bm, Cm = low[:, :R], low[:, R : R + N], low[:, R + N :]
    dt = jax.nn.softplus(rnd(delta) @ rnd(w["dt_proj"]) + w["dt_bias"])  # [S, di]
    A = -jnp.exp(w["A_log"]).T  # [di, N]

    def step(state, t):
        u_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t[:, None] * A) * state + (dt_t * u_t)[:, None] * B_t[None, :]
        return state, jnp.sum(state * C_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((di, N), jnp.float32), (u, Bm, Cm, dt))
    y = y + w["D"] * u
    return rnd(y * jax.nn.silu(z)) @ rnd(w["out_proj"]), y


def keys_values(x, w, d, rnd=identity):
    """x [S, D] -> (k [S, Hk, dh], v [S, Hk, dh])."""
    S, n = x.shape[0], d["Hk"] * d["dh"]
    kv = rnd(x) @ rnd(w["kv_proj"]) + w["kv_bias"]
    return kv[:, :n].reshape(S, d["Hk"], d["dh"]), kv[:, n:].reshape(S, d["Hk"], d["dh"])


def differential_attention(x, w, d, layer, k, v, window=None, rnd=identity):
    """x [S, D] -> [S, D] against keys and values ``k``, ``v [S, Hk, dh]`` (the
    layer's own, or the full layer's); one query pair at a time, so the two
    ``[S, S]`` score matrices of a long sequence stay small. ``layer`` is a
    traced index (``lam_init`` is computed from it)."""
    S, H, Hk, dh = x.shape[0], d["H"], d["Hk"], d["dh"]
    q = (rnd(x) @ rnd(w["q_proj"]) + w["q_bias"]).reshape(S, H // 2, 2, dh)
    k = k.reshape(S, Hk // 2, 2, dh)
    v = v.reshape(S, Hk // 2, 2 * dh)
    pos = jnp.arange(S)
    visible = pos[None, :] <= pos[:, None]
    if window is not None:
        visible = visible & (pos[:, None] - pos[None, :] < window)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * layer.astype(jnp.float32))
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + lam_init

    def attend(q_one, k_one, v_pair):  # [S, dh], [S, dh], [S, 2 dh] -> [S, 2 dh]
        s = rnd(q_one) @ rnd(k_one).T / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return rnd(p) @ rnd(v_pair)

    def pair(i):
        g = i // (H // Hk)
        o = attend(q[:, i, 0], k[:, g, 0], v[:, g]) - lam * attend(q[:, i, 1], k[:, g, 1], v[:, g])
        return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d["eps"]) * w["subln"] * (1.0 - lam_init)

    o = jax.lax.map(pair, jnp.arange(H // 2))  # [H / 2, S, 2 dh]
    return rnd(o.transpose(1, 0, 2).reshape(S, H * dh)) @ rnd(w["o_proj"]) + w["o_bias"]


def gated_memory(x, w, m, rnd=identity):
    return rnd(jax.nn.silu(rnd(x) @ rnd(w["in_proj"])) * m) @ rnd(w["out_proj"])


def mlp(x, w, d, rnd=identity):
    gu = rnd(x) @ rnd(w["gate_up"])
    return rnd(gu[:, d["F"] :] * jax.nn.silu(gu[:, : d["F"]])) @ rnd(w["down"])


def block(x, m, k, v, w, d, layer, kind, rnd=identity):
    """One layer of kind ``kind``: (x, m, k, v) -> (x, m, k, v). ``m`` is
    layer ``n/2``'s scan output and ``k``, ``v`` the full layer's keys and
    values, each handed down the stack from the layer that makes it."""
    h = layer_norm(x, w["norm1"], d["eps"])
    if kind in (MAMBA, MAMBA_MEMORY):
        y, scanned = mamba(h, w["ssm"], d, rnd)
        m = scanned if kind == MAMBA_MEMORY else m
    elif kind == GMU:
        y = gated_memory(h, w["gmu"], m, rnd)
    elif kind == CROSS:
        y = differential_attention(h, w["attn"], d, layer, k, v, None, rnd)
    else:
        own_k, own_v = keys_values(h, w["attn"], d, rnd)
        y = differential_attention(h, w["attn"], d, layer, own_k, own_v, d["window"] if kind == WINDOW else None, rnd)
        if kind == FULL:
            k, v = own_k, own_v
    x = x + y
    return x + mlp(layer_norm(x, w["norm2"], d["eps"]), w["mlp"], d, rnd), m, k, v


# ---- the weights as the serving configuration states them ----


def stated(tree):
    """Matrices were made in bfloat16 (the configuration's weights); widen
    them. The small leaves are float32 already."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_forward(d, rnd=identity):
    """``forward(key, tokens [S], at=None) -> logits [S or len(at), V]`` of one
    sequence, a layer's weights at a time (the whole model in float32 never
    sits on the device at once). The key is an argument of each program,
    never a constant of it: every seed runs the same compiled programs out
    of the persistent cache."""

    @jax.jit
    def embed(key, toks):
        return stated(W.make_outer(d, key, jnp.bfloat16, only=("embed",)))["embed"]["embedding"][toks]

    @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(1,))
    def layer(key, x, m, k, v, l, kind):
        return block(x, m, k, v, stated(W.make_layer(d, key, l, kind, jnp.bfloat16)), d, l, kind, rnd)

    @jax.jit
    def head(key, x, at):
        outer = stated(W.make_outer(d, key, jnp.bfloat16))
        h = layer_norm(x, outer["final_norm"], d["eps"])
        return rnd(h[at]) @ rnd(outer["embed"]["embedding"]).T

    def forward(key, tokens, at=None):
        S = tokens.shape[0]
        x = embed(key, tokens)
        m = jnp.zeros((S, d["di"]), jnp.float32)
        k = v = jnp.zeros((S, d["Hk"], d["dh"]), jnp.float32)
        for l, kind in enumerate(d["kinds"]):
            x, m, k, v = layer(key, x, m, k, v, jnp.int32(l), kind)
        return head(key, x, jnp.arange(S) if at is None else at)

    return forward


# ---- serving: the gap of each served token ----


def serve_gaps(d, key, tokens, first, count, width, *, control=False):
    """``tokens [n, T]``: each row a prompt followed by its served tokens,
    padded; served token ``i`` of row ``r`` is predicted at position
    ``first[r] + i`` for ``i < count[r]``. One row at a time. Returns arrays
    ``[n, width]``: the mask ``served``; ``gap`` = the reference's best logit
    minus the served token's logit; ``agree`` = the served token is the
    reference's own first choice; and, with ``control``, ``control_gap`` = the
    gap of the token that the reference with float8 (e4m3) operands in every
    product puts first."""
    n, T = tokens.shape
    idx = jnp.minimum(first[:, None] + jnp.arange(width)[None, :], T - 1)
    served_here = jnp.arange(width)[None, :] < count[:, None]
    served = jnp.take_along_axis(tokens, jnp.minimum(idx + 1, T - 1), axis=1)
    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap, agree, control_gap = [], [], []
    sound = make_forward(d)
    lower = make_forward(d, fp8_round) if control else None
    with highest():
        for r in range(n):
            ref = sound(key, tokens[r], idx[r])
            best = jnp.max(ref, axis=-1)
            gap.append(best - pick(ref, served[r]))
            agree.append(jnp.argmax(ref, axis=-1) == served[r])
            if control:
                low = jnp.argmax(lower(key, tokens[r], idx[r]), axis=-1)
                control_gap.append(best - pick(ref, low))
    out = {"served": served_here, "gap": jnp.stack(gap), "agree": jnp.stack(agree)}
    if control_gap:
        out["control_gap"] = jnp.stack(control_gap)
    return out


# ---- what reference_run.py calls ----


def serve_check(check: dict, control: bool) -> dict:
    import numpy as np

    d = W.dims(check["config"])
    reqs = check["requests"]
    pad_to = int(check["pad_to"])
    tokens = np.zeros((len(reqs), pad_to), np.int32)
    first, count = [], []
    for i, r in enumerate(reqs):
        seq = list(r["prompt"]) + list(r["tokens"])
        if len(seq) > pad_to:
            raise SystemExit(f"request of {len(seq)} tokens exceeds the mix's check_pad_to {pad_to}")
        tokens[i, : len(seq)] = seq
        first.append(len(r["prompt"]) - 1)
        count.append(len(r["tokens"]))
    res = serve_gaps(
        d, jax.random.key(check["seed"]), jnp.asarray(tokens), jnp.asarray(first), jnp.asarray(count),
        int(check["width"]), control=control,
    )
    served = np.asarray(res["served"])
    gaps = np.asarray(res["gap"])[served].tolist()
    out = {"requests": len(reqs), "positions": len(gaps), "agree": int(np.asarray(res["agree"])[served].sum()),
           "gap_max": max(gaps), "gap_mean": sum(gaps) / len(gaps)}
    if control:
        cgaps = np.asarray(res["control_gap"])[served].tolist()
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    return out


def train_check(check: dict, control: bool) -> dict:
    raise SystemExit("the phi4_flash family is served, not trained: it has no training reference")
