"""The plain reference of the ``jamba`` family: a Mamba-1 / attention hybrid
(arXiv:2403.19887; the ``jamba`` model class) in straightforward float32
``jax.numpy``. No cache, no batching, no chunks, no kernels: one sequence,
every layer on every token, the state-space scan as the sequential
recurrence it is defined by, attention as score matrices under a mask.

The equations (each departure from the published code is listed in the
configuration file's ``assumed``). Layer ``l``: ``h = x + mixer_l(RMSNorm(x))``,
``x' = h + W2 (u * silu(g))`` with ``[g | u] = RMSNorm'(h) W1``; after the last
layer a final RMSNorm and ``logits = x E^T`` with the embedding ``E``. No
position embedding anywhere. The mixer by the layer's kind
(``shape.layer_kinds``: attention iff ``(l - offset) % period == 0``):

- Mamba-1: ``[u | z] = x W_in``; ``c_t = b + sum_j w_j u_{t-K+1+j}`` (zeros
  before the sequence), ``u <- silu(c)``; ``[delta | B | C] = u W_x``; **an
  RMSNorm with a learned scale over each of the three**; ``dt_t =
  softplus(delta_t W_dt + b_dt)``; ``A = -exp(A_log)``; ``S_t[n, c] =
  exp(dt_t[c] A[n, c]) S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]`` from ``S_{-1} =
  0``; ``y_t[c] = sum_n S_t[n, c] C_t[n] + D[c] u_t[c]``; ``out = (y * silu(z))
  W_out``.
- attention: ``q = x W_q`` as ``H`` heads, ``k = x W_k``, ``v = x W_v`` as
  ``Hk`` heads of ``dh``; head ``i`` reads key/value head ``i // (H / Hk)``;
  ``P = softmax_t(q_i . k_t / sqrt(dh))`` over ``t <= p``; ``o_i = sum_t P_t
  v_t``; ``out = o W_o``. No bias, no window, no position embedding.

**Size.** A followed request is padded to the mix's ``check_pad_to`` (25k
positions in the cell): what is independent from row to row (the
feed-forward, and attention's queries) is computed in blocks of rows
(:func:`by_rows`), so no ``[S, 2F]`` or ``[H, S, S]`` array is ever whole.

**Precision.** Everything is float32 at ``jax.default_matmul_precision
("highest")``; ``rnd`` (the identity) is applied to both operands of every
matrix product, which is where the control, the next precision down, puts
float8 (e4m3) in (scaled a block of rows at a time where the product is
computed so). The weights are the configuration's: matrices made in bfloat16
and widened. Nothing routes, so EVERY served position is compared.

``serve_check`` is what ``reference_run.py`` calls (the contract is stated
there). ``train_check`` raises: this family is served, not trained.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.families._common import fp8_round, highest, identity, rms_norm

from . import weights as W
from .shape import MAMBA

ROWS = 512  # rows a block of :func:`by_rows` holds (attention: a score block of [20, 512, 25k] float32 is 1 GB)


def by_rows(fn, *xs, rows=ROWS):
    """``fn`` over arrays ``xs`` of ``S`` rows each, a block of ``rows`` rows
    at a time (the last padded with zeros, its surplus dropped): for what is
    independent from row to row."""
    S = xs[0].shape[0]
    if S <= rows:
        return fn(*xs)
    n = -(-S // rows)
    blocks = [jnp.concatenate([x, jnp.zeros((n * rows - S,) + x.shape[1:], x.dtype)]).reshape((n, rows) + x.shape[1:])
              for x in xs]
    out = jax.lax.map(lambda block: fn(*block), tuple(blocks))
    return out.reshape((n * rows,) + out.shape[2:])[:S]


# ---- the mixers ----


def mamba(x, w, d, rnd=identity, inner_norms=True):
    """x [S, D] -> [S, D]: the sequential recurrence from a zero state.
    ``inner_norms=False`` leaves the three inner RMSNorms out (a test shows
    that they matter)."""
    S = x.shape[0]
    di, N, K, R = d["di"], d["N"], d["K"], d["R"]
    proj = rnd(x) @ rnd(w["in_proj"])
    u, z = proj[:, :di], proj[:, di:]
    window = jnp.concatenate([jnp.zeros((K - 1, di), jnp.float32), u])
    u = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * window[j : j + S] for j in range(K)))
    low = rnd(u) @ rnd(w["x_proj"])
    delta, Bm, Cm = low[:, :R], low[:, R : R + N], low[:, R + N :]
    if inner_norms:
        delta, Bm, Cm = (rms_norm(a, w[n], d["eps"]) for a, n in zip((delta, Bm, Cm), W.INNER_NORMS))
    dt = jax.nn.softplus(rnd(delta) @ rnd(w["dt_proj"]) + w["dt_bias"])  # [S, di]
    A = -jnp.exp(w["A_log"])  # [N, di]

    def step(state, t):
        u_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t[None, :] * A) * state + (dt_t * u_t)[None, :] * B_t[:, None]
        return state, jnp.sum(state * C_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, di), jnp.float32), (u, Bm, Cm, dt))
    y = y + w["D"] * u
    return rnd(y * jax.nn.silu(z)) @ rnd(w["out_proj"])


def attention(x, w, d, rnd=identity):
    """x [S, D] -> [S, D]: causal grouped-query attention without positions,
    a block of query rows at a time against every key."""
    S, H, Hk, dh = x.shape[0], d["H"], d["Hk"], d["dh"]
    q = jnp.einsum("sd,dhe->she", rnd(x), rnd(w["q_proj"]))
    k = jnp.einsum("sd,dke->ske", rnd(x), rnd(w["k_proj"]))
    v = jnp.einsum("sd,dke->ske", rnd(x), rnd(w["v_proj"]))
    kr, vr = rnd(k), rnd(v)
    cols = jnp.arange(S)

    def rows(qb, at):  # [rows, H, dh] queries standing at [rows] -> [rows, H dh]
        qb = qb.reshape(qb.shape[0], Hk, H // Hk, dh)
        s = jnp.einsum("rkge,ske->kgrs", rnd(qb), kr) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(cols[None, :] <= at[:, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgrs,ske->rkge", rnd(p), vr).reshape(qb.shape[0], H * dh)

    o = by_rows(rows, q, cols)
    return rnd(o) @ rnd(w["o_proj"])


def mlp(x, w, d, rnd=identity):
    def rows(xb):
        gu = rnd(xb) @ rnd(w["gate_up"])
        return rnd(gu[:, d["F"] :] * jax.nn.silu(gu[:, : d["F"]])) @ rnd(w["down"])

    return by_rows(rows, x)


def block(x, w, d, kind, rnd=identity, inner_norms=True):
    """One layer of kind ``kind``: x [S, D] -> x [S, D]."""
    h = rms_norm(x, w["norm1"]["scale"], d["eps"])
    x = x + (mamba(h, w["ssm"], d, rnd, inner_norms) if kind == MAMBA else attention(h, w["attn"], d, rnd))
    return x + mlp(rms_norm(x, w["norm2"]["scale"], d["eps"]), w["mlp"], d, rnd)


# ---- the weights as the serving configuration states them ----


def stated(tree):
    """Matrices were made in bfloat16 (the configuration's weights); widen
    them. The small leaves are float32 already."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_forward(d, rnd=identity, inner_norms=True):
    """``forward(key, tokens [S], at=None) -> logits [S or len(at), V]`` of one
    sequence, a layer's weights at a time (the whole model in float32 never
    sits on the device at once). The key is an argument of each program,
    never a constant of it: every seed runs the same compiled programs out
    of the persistent cache."""

    @jax.jit
    def embed(key, toks):
        return stated(W.make_outer(d, key, jnp.bfloat16, only=("embed",)))["embed"]["embedding"][toks]

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(1,))
    def layer(key, x, l, kind):
        return block(x, stated(W.make_layer(d, key, l, kind, jnp.bfloat16)), d, kind, rnd, inner_norms)

    @jax.jit
    def head(key, x, at):
        outer = stated(W.make_outer(d, key, jnp.bfloat16))
        h = rms_norm(x[at], outer["final_norm"]["scale"], d["eps"])
        return rnd(h) @ rnd(outer["embed"]["embedding"]).T

    def forward(key, tokens, at=None):
        x = embed(key, tokens)
        for l, kind in enumerate(d["kinds"]):
            x = layer(key, x, jnp.int32(l), kind)
        return head(key, x, jnp.arange(tokens.shape[0]) if at is None else at)

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
    raise SystemExit("the jamba family is served, not trained: it has no training reference")
