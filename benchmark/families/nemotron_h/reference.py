"""The plain reference of the ``nemotron_h`` family: a list of pre-norm
residual layers of one mixer each (RMSNorm, no biases but the convolution's,
untied head) in straightforward float32 ``jax.numpy``. No cache, no
batching, no chunks, no kernels: one sequence, the whole forward, the
state-space scan as the sequential recurrence it is defined by.

The equations (ISSUE 33 A; each departure from the published code is listed
in the configuration file's ``assumed``). ``x <- x + mixer_l(RMSNorm(x))``,
the mixer by the layer's character in ``hybrid_override_pattern``:

- ``M``, Mamba-2: ``[z | xBC | dt] = x W_in``; ``c_t = b + sum_j w_j
  xBC_{t-K+1+j}`` (zeros before the sequence), ``silu``; ``[u | B | C]``, u as
  ``mH`` heads of ``mP``, head h reading group ``h // (mH / G)`` of B and C;
  ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt_t
  A) S_{t-1} + dt_t u_t (x) B_t`` from ``S_{-1} = 0``, ``y_t = S_t C_t + D
  u_t``; ``y <- y silu(z)``, RMSNorm over each of the G groups of ``di / G``
  channels with a learned scale, ``W_out``;
- ``*``, attention: ``q = x Wq [H, dh]``, ``k, v = x Wk, x Wv [Hk, dh]``, ``p =
  softmax_j(q_i k_j / sqrt(dh))`` over ``j <= i``, NO position embedding,
  heads concatenated, ``Wo``;
- ``E``, experts: ``g = sigmoid(x Wr)`` over all ``E`` experts; the ``k``
  selected are the top k of ``g + e_bias``; weights ``scale g_e / sum of the
  selected g``; the output is the sum over the selected experts THAT LIE IN
  ``held`` of ``w_e relu(x Wu_e)^2 Wd_e`` — the chip's share, renormalised
  over all k selected; what the absent experts would add is left out, here
  as in the program — plus the shared expert ``relu(x Wu_s)^2 Wd_s``,
  unweighted.

**Precision.** Everything is float32 at ``jax.default_matmul_precision
("highest")``; ``rnd`` (the identity) is applied to both operands of every
matrix product, which is where the control, the next precision down, puts
float8 (e4m3) in. The weights are the configuration's: matrices made in
bfloat16 and widened. A reference that also rounded every stored activation
to bfloat16, where a bfloat16 program must, was tried (ISSUE 33 F): one
element that rounds the other way moves every output of the next product a
little, 1% of them across a rounding boundary, so after three layers the
two computations round alike nowhere and the rounding reference is one more
bfloat16 realisation, as far from the program as the program is from this
one, with its own selections (PERF.md section 6, PR 33).

**Positions that are not compared.** Selecting the top k of E scores is not
continuous: where a held expert lies within the configuration's
``check.edge`` of the selection's edge (an outsider that close below the
k-th, or an insider that close above the (k+1)-th), a rounding selects
another expert and the layer's output jumps by that expert's whole part, in
any precision. The configuration file states the band, set from the measured
disagreement of program and reference scores (its ``check`` and PERF.md
section 6, PR 33, have the readings); ``serve_check`` reports how many
positions it left out, and how the gap depends on the margin
(``edge_profile``), so the band can be re-read from any run.

``serve_check`` is what ``reference_run.py`` calls (the contract is stated
there). ``train_check`` raises: this family is served, not trained.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.families._common import fp8_round, highest, identity, rms_norm

from . import weights as W

PROFILE = tuple(2.0 ** -e for e in range(7, 15))  # margins the edge profile is read at


# ---- the mixers ----


def mamba(x, w, d, rnd=identity):
    """x [S, D] -> [S, D]: the sequential recurrence from a zero state."""
    S = x.shape[0]
    H, P, G, N, K, di = d["mH"], d["mP"], d["G"], d["N"], d["K"], d["di"]
    proj = rnd(x) @ rnd(w["in_proj"])
    z, xBC, dt = proj[:, :di], proj[:, di : di + d["C"]], proj[:, di + d["C"] :]
    window = jnp.concatenate([jnp.zeros((K - 1, d["C"]), jnp.float32), xBC])
    conv = jax.nn.silu(w["conv_b"] + sum(w["conv_w"][j] * window[j : j + S] for j in range(K)))
    u = conv[:, :di].reshape(S, H, P)
    Bm = jnp.repeat(conv[:, di : di + G * N].reshape(S, G, N), H // G, axis=1)  # [S, H, N]
    Cm = jnp.repeat(conv[:, di + G * N :].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # [S, H]
    A = -jnp.exp(w["A_log"])

    def step(state, t):
        u_t, B_t, C_t, dt_t = t
        state = jnp.exp(dt_t * A)[:, None, None] * state + (dt_t[:, None] * u_t)[:, :, None] * B_t[:, None, :]
        return state, jnp.sum(state * C_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (u, Bm, Cm, dt))
    y = (y + w["D"][:, None] * u).reshape(S, di) * jax.nn.silu(z)
    y = y.reshape(S, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["eps"])
    y = y.reshape(S, di) * w["norm_scale"]
    return rnd(y) @ rnd(w["out_proj"])


def attention(x, w, d, rnd=identity):
    """x [S, D] -> [S, D]; one key/value head's group of query heads at a
    time, so the [S, S] scores of a long sequence stay small."""
    S = x.shape[0]
    H, Hk, dh = d["H"], d["Hk"], d["dh"]
    x = rnd(x)
    q = jnp.einsum("sd,dhe->she", x, rnd(w["q_proj"])).reshape(S, Hk, H // Hk, dh)
    k = jnp.einsum("sd,dke->ske", x, rnd(w["k_proj"]))
    v = jnp.einsum("sd,dke->ske", x, rnd(w["v_proj"]))
    pos = jnp.arange(S)
    visible = pos[None, :] <= pos[:, None]

    def group(args):
        qg, kg, vg = args  # [S, G, dh], [S, dh], [S, dh]
        s = jnp.einsum("sge,te->gst", rnd(qg), rnd(kg)) / jnp.sqrt(float(dh))
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("gst,te->sge", rnd(p), rnd(vg))

    out = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(S, H * dh)  # [Hk, S, G, dh] -> [S, H dh]
    return rnd(out) @ rnd(w["o_proj"])


def route(x, w, d, rnd=identity, omit=None):
    """(selected ids [S, k], their weights [S, k], margin [S]) over all E
    experts; ``margin``: how far the nearest HELD expert lies from the
    selection's edge (an outsider below the k-th score, an insider above the
    (k+1)-th): under the configuration's ``check.edge`` the selection hangs
    on less than the stated precision decides."""
    g = jax.nn.sigmoid(rnd(x) @ rnd(w["router"]))
    biased = g + (0.0 if omit == "e_bias" else w["e_bias"])
    top, idx = jax.lax.top_k(biased, d["k"] + 1)
    kth, next_ = top[:, d["k"] - 1 : d["k"]], top[:, d["k"] :]
    first, n = d["held"]
    held = biased[:, first : first + n]
    margin = jnp.min(jnp.where(held <= next_, kth - held, held - next_), axis=-1)
    idx = idx[:, : d["k"]]
    picked = jnp.take_along_axis(g, idx, axis=-1)
    return idx, d["scale"] * picked / jnp.sum(picked, axis=-1, keepdims=True), margin


def relu2_mlp(x, up, down, rnd=identity, gate=None):
    h = jnp.square(jax.nn.relu(rnd(x) @ rnd(up)))
    return rnd(h if gate is None else h * gate[:, None]) @ rnd(down)


def experts(x, w, d, rnd=identity, omit=None, with_margin=False):
    """The held experts' part of the layer for x [S, D], without the shared
    expert (and, asked, the selection's margin)."""
    first, n = d["held"]
    idx, wt, margin = route(x, w, d, rnd, omit)
    gates = jnp.zeros((x.shape[0], d["E"]), jnp.float32)
    gates = jax.vmap(lambda g, i, v: g.at[i].add(v))(gates, idx, wt)[:, first : first + n]  # [S, n]
    one = lambda args: relu2_mlp(x, args[0], args[1], rnd, gate=args[2])
    y = jnp.sum(jax.lax.map(one, (w["w_up"], w["w_down"], gates.T)), axis=0)
    return (y, margin) if with_margin else y


def shared_expert(x, w, rnd=identity):
    return relu2_mlp(x, w["up_proj"], w["down_proj"], rnd)


def block(x, w, d, kind, rnd=identity, omit=None):
    """One layer: (x [S, D], margin [S]); infinite where the layer selects nothing."""
    h = rms_norm(x, w["norm"]["scale"], d["eps"])
    margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    if kind == W.MAMBA:
        y = mamba(h, w["ssm"], d, rnd)
    elif kind == W.ATTENTION:
        y = attention(h, w["attn"], d, rnd)
    else:
        y, margin = experts(h, w["moe"], d, rnd, omit, with_margin=True)
        y = y + shared_expert(h, w["shared"], rnd)
    return x + y, margin


# ---- the weights as the serving configuration states them ----


def stated(tree):
    """Matrices were made in bfloat16 (the configuration's weights); widen
    them. The small leaves are float32 already."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_forward(d, rnd=identity, omit=None):
    """``forward(key, tokens [S], at=None, with_margin=False) -> logits [S or
    len(at), V]`` of one sequence, a layer at a time (asked, also the
    smallest margin of any expert layer's selection, at the same positions).
    The key is an argument of each program, never a constant of it: every
    seed runs the same compiled programs out of the persistent cache."""

    @jax.jit
    def embed(key, toks):
        return stated(W.make_outer(d, key, jnp.bfloat16, only=("embed",)))["embed"]["embedding"][toks]

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(1,))
    def layer(key, x, l, kind):
        return block(x, stated(W.make_layer(d, key, l, kind, jnp.bfloat16)), d, kind, rnd, omit)

    @jax.jit
    def head(key, x, at):
        outer = stated(W.make_outer(d, key, jnp.bfloat16, only=("final_norm", "lm_head")))
        h = rms_norm(x, outer["final_norm"]["scale"], d["eps"])
        return rnd(h[at]) @ rnd(outer["lm_head"]["kernel"])

    def forward(key, tokens, at=None, with_margin=False):
        x, margin = embed(key, tokens), jnp.inf
        for l, kind in enumerate(d["kinds"]):
            x, margin_l = layer(key, x, jnp.int32(l), kind)
            margin = jnp.minimum(margin, margin_l)
        at = jnp.arange(tokens.shape[0]) if at is None else at
        logits = head(key, x, at)
        return (logits, margin[at]) if with_margin else logits

    return forward


# ---- serving: the gap of each served token ----


def serve_gaps(d, key, tokens, first, count, width, *, control=False, omit=None):
    """``tokens [n, T]``: each row a prompt followed by its served tokens,
    padded; served token ``i`` of row ``r`` is predicted at position
    ``first[r] + i`` for ``i < count[r]``. One row at a time. Returns arrays
    ``[n, width]``: the mask ``served`` and each position's ``margin`` (the
    smallest distance of a held expert from a selection's edge over the
    expert layers); ``gap`` = the reference's best logit minus the served
    token's logit; ``agree`` = the served token is the reference's own first
    choice; and, with ``control``, ``control_gap`` = the gap of the token
    that the reference with float8 (e4m3) operands in every product puts
    first."""
    n, T = tokens.shape
    idx = jnp.minimum(first[:, None] + jnp.arange(width)[None, :], T - 1)
    served_here = jnp.arange(width)[None, :] < count[:, None]
    served = jnp.take_along_axis(tokens, jnp.minimum(idx + 1, T - 1), axis=1)
    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap, agree, control_gap, margin = [], [], [], []
    sound = make_forward(d, omit=omit)
    lower = make_forward(d, fp8_round, omit) if control else None
    with highest():
        for r in range(n):
            ref, margin_r = sound(key, tokens[r], idx[r], with_margin=True)
            margin.append(margin_r)
            best = jnp.max(ref, axis=-1)
            gap.append(best - pick(ref, served[r]))
            agree.append(jnp.argmax(ref, axis=-1) == served[r])
            if control:
                low = jnp.argmax(lower(key, tokens[r], idx[r]), axis=-1)
                control_gap.append(best - pick(ref, low))
    out = {"served": served_here, "margin": jnp.stack(margin), "gap": jnp.stack(gap), "agree": jnp.stack(agree)}
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
        int(check["width"]), control=control, omit=check["config"]["bench"].get("reference_omits"),
    )
    served, margin, gap = (np.asarray(res[k]) for k in ("served", "margin", "gap"))
    edge = d["edge"]
    valid, near = served & (margin >= edge), served & (margin < edge)
    gaps = gap[valid].tolist()
    if not gaps:
        raise SystemExit("every served position lies near a selection's edge: nothing to compare")
    # For the record, not compared: the positions left out, the gap with them in, and by the margin a position
    # would have to keep: how many positions remain and the largest gap among them.
    profile = {f"{m:g}": [int((served & (margin >= m)).sum()), float(gap[served & (margin >= m)].max(initial=0.0))]
               for m in PROFILE}
    out = {"requests": len(reqs), "positions": len(gaps), "agree": int(np.asarray(res["agree"])[valid].sum()),
           "gap_max": max(gaps), "gap_mean": sum(gaps) / len(gaps), "edge": edge,
           "positions_near_edge": int(near.sum()), "gap_max_all_positions": float(gap[served].max()),
           "edge_profile": profile}
    print(f"positions {len(gaps)} compared, positions_near_edge {int(near.sum())} left out (check.edge {edge:g}); "
          f"margin -> [positions kept, largest gap]: {profile}", flush=True)
    if control:
        cgaps = np.asarray(res["control_gap"])[valid].tolist()
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    return out


def train_check(check: dict, control: bool) -> dict:
    raise SystemExit("the nemotron_h family is served, not trained: it has no training reference")
