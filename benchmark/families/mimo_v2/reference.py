"""The plain reference of the ``mimo_v2`` family: a list of pre-norm
residual layers (RMSNorm, no biases, untied head), each an attention kind
and a feed-forward kind, in straightforward float32 ``jax.numpy``. No
cache, no batching, no kernels: one sequence, the whole forward.

The equations (ISSUE 28 A; each departure from the published code is listed
in the configuration file's ``assumed``). For layer ``l`` of kind
``(full | window, dense | moe)``:

- attention: ``q = x Wq [H, dqk]``, ``k = x Wk [Hk, dqk]``, ``v = x Wv [Hk, dv]``,
  ``Hk`` by kind; rotate-half rotary embedding on the first ``rot``
  components of q and k with the kind's theta, the rest pass through;
  ``v <- vscale v``; ``s_ij = q_i k_j / sqrt(dqk)``; key j is visible to
  query i iff ``j <= i`` and, in a window layer, ``i - j < window``; with a
  sink logit ``b_h`` (the kinds that have one): ``p_ij = exp(s_ij - m) /
  (exp(b_h - m) + sum_j' exp(s_ij' - m))``, else plain softmax;
  ``o_i = sum_j p_ij v_j``, heads concatenated, ``Wo``;
- dense feed-forward: ``(silu(x Wg) * (x Wu)) Wd``;
- experts: ``g = sigmoid(x Wr)`` over all ``E`` experts of the layer; the
  ``k`` selected are the top k of ``g + e_bias``; weights ``g_e / sum of the
  selected g`` (the bias selects and does not weigh); the output is the sum
  over the selected experts THAT LIE IN ``held`` of ``w_e (silu(x Wg_e) *
  (x Wu_e)) Wd_e``: the chip's share, renormalised over all k selected, and
  what the absent experts would add is left out, here as in the program.

It imports nothing of the program under test: weights come from the
family's ``weights.py`` and the seed, one layer at a time and in the
precision the configuration states (matrices rounded to bfloat16, then
widened), so the float32 weights of the whole model never sit on the chip
together. Matrix products run at ``jax.default_matmul_precision("highest")``.

**Positions that are not compared.** Selecting the top k of E scores is not
continuous: where the k-th and the (k+1)-th score lie closer than the stated
precision resolves, a rounding in the last place selects another expert, the
layer's output jumps by that expert's whole part, and either selection is
the model. bfloat16 holds a score in (0, 1) to 8 bits, so wherever, in the
reference's own routing, an expert HELD HERE lies within ``EDGE`` = 2^-8 of
the selection's edge in some expert layer (an outsider that close below the
k-th, or an insider that close above the (k+1)-th), the token's logits are not
compared; an expert that is not held adds nothing here either way. On the
chip that leaves out about a third of the positions and takes the sound gap
from 0.12-0.24 (one flipped expert's part, in any precision) to under 0.03,
while float8 operands still read 0.25 or more (PERF.md section 6, PR 28).
``serve_check`` reports how many positions it left out.

``serve_check`` is what ``reference_run.py`` calls (the contract is stated
there). ``train_check`` raises: this family is served, not trained. A
configuration's ``bench.reference_omits`` (``"sink"`` or ``"e_bias"``; the
benchmark's own tests state it, no cell does) makes the reference leave
that mechanism out, to show that the check notices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.families._common import fp8_round, highest, identity, rms_norm

from . import weights as W

EDGE = 2.0 ** -8  # bfloat16's resolution of a score in (0, 1): see "Positions that are not compared"


# ---- the layer ----


def rope(x, positions, theta, rot):
    """x [S, heads, d], positions [S]; rotate-half on the first ``rot``."""
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention(x, w, d, attn_kind, rnd=identity, omit=None):
    """x [S, D] -> [S, D]; one key/value head's group of query heads at a
    time, so the [S, S] scores of a long sequence stay small."""
    S = x.shape[0]
    H, Hk, dqk, dv = d["H"], d["Hk"][attn_kind], d["dqk"], d["dv"]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = rnd(x)
    q = jnp.einsum("sd,dhe->she", x, rnd(w["q_proj"]))
    k = jnp.einsum("sd,dke->ske", x, rnd(w["k_proj"]))
    v = jnp.einsum("sd,dke->ske", x, rnd(w["v_proj"])) * d["vscale"]
    q, k = rope(q, pos, d["theta"][attn_kind], d["rot"]), rope(k, pos, d["theta"][attn_kind], d["rot"])
    q = q.reshape(S, Hk, H // Hk, dqk)
    i, j = pos[:, None], pos[None, :]
    visible = j <= i
    if attn_kind == W.WINDOW:
        visible = visible & (i - j < d["window"])
    has_sink = d["sink"][attn_kind] and omit != "sink"
    sink = (w["sink"] if has_sink else jnp.zeros((H,), jnp.float32)).reshape(Hk, H // Hk)

    def group(args):
        qg, kg, vg, b = args  # [S, G, dqk], [S, dqk], [S, dv], [G]
        s = jnp.einsum("sge,te->gst", rnd(qg), rnd(kg)) / jnp.sqrt(float(dqk))
        s = jnp.where(visible, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        if has_sink:
            m = jnp.maximum(m, b[:, None, None])
        e = jnp.exp(s - m)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        if has_sink:
            denom = denom + jnp.exp(b[:, None, None] - m)
        return jnp.einsum("gst,te->sge", rnd(e / denom), rnd(vg))

    out = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2), sink))
    out = out.transpose(1, 0, 2, 3).reshape(S, H * dv)  # [Hk, S, G, dv] -> [S, H dv]
    return rnd(out) @ rnd(w["o_proj"])


def dense_mlp(x, w, rnd=identity):
    x = rnd(x)
    h = jax.nn.silu(x @ rnd(w["gate_proj"])) * (x @ rnd(w["up_proj"]))
    return rnd(h) @ rnd(w["down_proj"])


def route(x, w, d, rnd=identity, omit=None):
    """(selected ids [S, k], their weights [S, k], near [S]) over all E
    experts; ``near``: a held expert lies within ``EDGE`` of the selection's
    edge, so the selection hangs on less than bfloat16 resolves."""
    g = jax.nn.sigmoid(rnd(x) @ rnd(w["router"]))
    biased = g + (0.0 if omit == "e_bias" else w["e_bias"])
    top, idx = jax.lax.top_k(biased, d["k"] + 1)
    kth, next_ = top[:, d["k"] - 1 : d["k"]], top[:, d["k"] :]
    first, n = d["held"]
    held = biased[:, first : first + n]
    near = jnp.any(((held <= next_) & (kth - held < EDGE)) | ((held >= kth) & (held - next_ < EDGE)), axis=-1)
    idx = idx[:, : d["k"]]
    picked = jnp.take_along_axis(g, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True), near


def experts(x, w, d, rnd=identity, omit=None, with_near=False):
    """The held experts' part of the layer for x [S, D] (and, asked, where
    its selection was near its edge)."""
    first, n = d["held"]
    idx, wt, near = route(x, w, d, rnd, omit)
    gates = jnp.zeros((x.shape[0], d["E"]), jnp.float32)
    gates = jax.vmap(lambda g, i, v: g.at[i].add(v))(gates, idx, wt)[:, first : first + n]  # [S, n]

    def one(args):
        wg, wu, wd, gate = args
        return gate[:, None] * dense_mlp(x, {"gate_proj": wg, "up_proj": wu, "down_proj": wd}, rnd)

    y = jnp.sum(jax.lax.map(one, (w["w_gate"], w["w_up"], w["w_down"], gates.T)), axis=0)
    return (y, near) if with_near else y


def block(x, w, d, kind, rnd=identity, omit=None):
    """One layer: (x [S, D], near [S]); a dense layer's ``near`` is all false."""
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], d["eps"]), w["attn"], d, kind[0], rnd, omit)
    h = rms_norm(x, w["mlp_norm"]["scale"], d["eps"])
    if kind[1] == W.DENSE:
        return x + dense_mlp(h, w["mlp"], rnd), jnp.zeros((x.shape[0],), bool)
    y, near = experts(h, w["moe"], d, rnd, omit, with_near=True)
    return x + y, near


# ---- the weights as the serving configuration states them ----


def stated(tree):
    """Matrices were made in bfloat16 (the configuration's weights); widen
    them. Norm scales, sinks and the selection bias are float32 already."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_forward(d, rnd=identity, omit=None):
    """``forward(key, tokens [S], at=None, with_near=False) -> logits [S or
    len(at), V]`` of one sequence, a layer at a time (asked, also where any
    expert layer's selection was near its edge, at the same positions). The
    key is an argument of each program, never a constant of it: every seed
    runs the same compiled programs out of the persistent cache."""

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

    def forward(key, tokens, at=None, with_near=False):
        x, near = embed(key, tokens), False
        for l, kind in enumerate(d["kinds"]):
            x, near_l = layer(key, x, jnp.int32(l), kind)
            near = near | near_l
        at = jnp.arange(tokens.shape[0]) if at is None else at
        logits = head(key, x, at)
        return (logits, near[at]) if with_near else logits

    return forward


# ---- serving: the gap of each served token ----


def serve_gaps(d, key, tokens, first, count, width, *, control=False, omit=None):
    """``tokens [n, T]``: each row a prompt followed by its served tokens,
    padded; served token ``i`` of row ``r`` is predicted at position
    ``first[r] + i`` for ``i < count[r]``. One row at a time. Returns
    arrays ``[n, width]`` with the masks ``valid`` (served positions that are
    compared) and ``near`` (served positions left out: a held expert within
    ``EDGE`` of the selection's edge in some layer): ``gap`` = the reference's
    best logit minus the served token's logit; ``agree`` = the served token
    is the reference's own first choice; and, with ``control``,
    ``control_gap`` = the gap of the token that the reference with float8
    (e4m3) operands in every product puts first."""
    n, T = tokens.shape
    idx = jnp.minimum(first[:, None] + jnp.arange(width)[None, :], T - 1)
    served_here = jnp.arange(width)[None, :] < count[:, None]
    served = jnp.take_along_axis(tokens, jnp.minimum(idx + 1, T - 1), axis=1)
    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap, agree, control_gap, near = [], [], [], []
    sound = make_forward(d, omit=omit)
    lower = make_forward(d, rnd=fp8_round, omit=omit) if control else None
    with highest():
        for r in range(n):
            ref, near_r = sound(key, tokens[r], idx[r], with_near=True)
            near.append(near_r)
            best = jnp.max(ref, axis=-1)
            gap.append(best - pick(ref, served[r]))
            agree.append(jnp.argmax(ref, axis=-1) == served[r])
            if control:
                low = jnp.argmax(lower(key, tokens[r], idx[r]), axis=-1)
                control_gap.append(best - pick(ref, low))
    near = jnp.stack(near)
    out = {"valid": served_here & ~near, "near": served_here & near, "gap": jnp.stack(gap), "agree": jnp.stack(agree)}
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
    valid, near = np.asarray(res["valid"]), np.asarray(res["near"])
    gaps = np.asarray(res["gap"])[valid].tolist()
    if not gaps:
        raise SystemExit("every served position lies near a selection's edge: nothing to compare")
    out = {"requests": len(reqs), "positions": len(gaps), "agree": int(np.asarray(res["agree"])[valid].sum()),
           "gap_max": max(gaps), "gap_mean": sum(gaps) / len(gaps),
           # for the record, not compared: the positions left out, and the gap with them in
           "positions_near_edge": int(near.sum()), "gap_max_all_positions": float(np.asarray(res["gap"])[valid | near].max())}
    if control:
        cgaps = np.asarray(res["control_gap"])[valid].tolist()
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    return out


def train_check(check: dict, control: bool) -> dict:
    raise SystemExit("the mimo_v2 family is served, not trained: it has no training reference")
