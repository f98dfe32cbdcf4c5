"""The plain reference of the ``exaone_moe`` family (K-EXAONE-236B-A23B): a
list of pre-norm residual layers (RMSNorm, no biases, untied head), each an
attention kind and a feed-forward kind, and the model's one
multi-token-prediction block, in straightforward float32 ``jax.numpy``. No
cache, no batching, no kernels, no drafting: one sequence, the whole forward.

The equations (ISSUE 41; what the published configuration does not state is
listed, with its reason, in the configuration file's ``assumed``). For layer
``l`` of kind ``(full | window, dense | moe)``:

- block: ``x += Attn(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``;
- attention: ``q = RMSNorm_dh(x Wq)`` and ``k = RMSNorm_dh(x Wk)`` a head (a
  learned scale ``[dh]`` each), ``v = x Wv``; rotate-half rotary embedding
  over all ``dh`` components of q and k IN WINDOW LAYERS ONLY, none in full
  layers; ``s_ij = q_i k_j / sqrt(dh)``; key j is visible to query i iff ``j
  <= i`` and, in a window layer, ``i - j < window``; plain softmax (no sink);
  ``o_i = sum_j p_ij v_j``, heads concatenated, ``Wo``;
- dense feed-forward (layer 0): ``(silu(x Wg) * (x Wu)) Wd``;
- sparse: ``y = Shared(x) + scale * sum_{e in top k} w_e Expert_e(x)``: ``g
  = sigmoid(x Wr)`` over all ``E`` experts; the ``k`` selected are the top k
  of ``g + e_bias``; ``w_e = g_e /`` the selected ``g``'s sum (the bias
  selects and does not weigh); the sum runs over the selected experts THAT
  LIE IN ``held``: the chip's share, renormalised over all k selected, and
  what the absent experts would add is left out, here as in the program. The
  shared expert is whole on every chip;
- the multi-token-prediction block: ``u_i = [RMSNorm(Emb(x_{i+1})) ;
  RMSNorm(h_i)] W_eh`` with ``h_i`` the main stack's hidden state BEFORE its
  final norm, one layer of kind (full, moe), a final norm of its own, the
  main model's embedding and head; its logits at ``i`` predict ``x_{i+2}``.

It imports nothing of the program under test: weights come from the
family's ``weights.py`` and the seed, one layer at a time and in the
precision the configuration states (matrices rounded to bfloat16, then
widened), so the float32 weights of the whole model never sit on the chip
together. Matrix products run at ``jax.default_matmul_precision("highest")``.

**Positions that are not compared** (the ``mimo_v2`` family's rule): selecting
the top k of E scores is not continuous, so wherever, in the reference's own
routing, an expert HELD HERE lies within ``EDGE`` = 2^-8 (bfloat16's
resolution of a score in (0, 1)) of the selection's edge in some sparse layer
of the main stack, the token's logits are not compared. ``serve_check``
reports how many positions it left out.

**The drafts.** The harness's record holds the tokens served, not the drafts
the program verified on the way. ``serve_check`` therefore also follows the
block teacher-forced over each request and returns ``draft_agree_pct``: of
the served tokens after a request's first, the share that the block's own
first choice, two positions back, equals. That is what the engine's
``mtp_accept_pct`` counts on the row-steps it verified, so the two lie
together when the program's block computes what this one does.

``serve_check`` is what ``reference_run.py`` calls (the contract is stated
there). ``train_check`` raises: this family is served, not trained.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.families._common import fp8_round, highest, identity, rms_norm

from . import weights as W

EDGE = 2.0 ** -8  # bfloat16's resolution of a score in (0, 1): see "Positions that are not compared"


# ---- the layer ----


def rope(x, positions, theta):
    """x [S, heads, d], positions [S]; rotate-half over all of d."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, d, attn_kind, rnd=identity):
    """x [S, D] -> [S, D]; one key/value head's group of query heads at a
    time, so the [S, S] scores of a long sequence stay small."""
    S = x.shape[0]
    H, Hk, dh = d["H"], d["Hk"], d["dh"]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = rnd(x)
    q = rms_norm(jnp.einsum("sd,dhe->she", x, rnd(w["q_proj"])), w["q_norm"], d["eps"])
    k = rms_norm(jnp.einsum("sd,dke->ske", x, rnd(w["k_proj"])), w["k_norm"], d["eps"])
    v = jnp.einsum("sd,dke->ske", x, rnd(w["v_proj"]))
    i, j = pos[:, None], pos[None, :]
    visible = j <= i
    if attn_kind == W.WINDOW:
        q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
        visible = visible & (i - j < d["window"])
    q = q.reshape(S, Hk, H // Hk, dh)

    def group(args):
        qg, kg, vg = args  # [S, G, dh], [S, dh], [S, dh]
        s = jnp.einsum("sge,te->gst", rnd(qg), rnd(kg)) / jnp.sqrt(float(dh))
        p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
        return jnp.einsum("gst,te->sge", rnd(p), rnd(vg))

    out = jax.lax.map(group, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    out = out.transpose(1, 0, 2, 3).reshape(S, H * dh)  # [Hk, S, G, dh] -> [S, H dh]
    return rnd(out) @ rnd(w["o_proj"])


def swiglu(x, w, rnd=identity):
    x = rnd(x)
    h = jax.nn.silu(x @ rnd(w["gate_proj"])) * (x @ rnd(w["up_proj"]))
    return rnd(h) @ rnd(w["down_proj"])


def route(x, w, d, held, rnd=identity):
    """(selected ids [S, k], their weights [S, k], near [S]) over all E
    experts; ``near``: an expert of ``held`` lies within ``EDGE`` of the
    selection's edge, so the selection hangs on less than bfloat16 resolves."""
    g = jax.nn.sigmoid(rnd(x) @ rnd(w["router"]))
    biased = g + w["e_bias"]
    top, idx = jax.lax.top_k(biased, d["k"] + 1)
    kth, next_ = top[:, d["k"] - 1 : d["k"]], top[:, d["k"] :]
    first, n = held
    mine = biased[:, first : first + n]
    near = jnp.any(((mine <= next_) & (kth - mine < EDGE)) | ((mine >= kth) & (mine - next_ < EDGE)), axis=-1)
    idx = idx[:, : d["k"]]
    picked = jnp.take_along_axis(g, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True), near


def routed(x, w, d, held, rnd=identity):
    """``scale * sum`` over the selected experts that lie in ``held`` (whose
    matrices ``w`` holds) for x [S, D], and where the selection was near its edge."""
    first, n = held
    idx, wt, near = route(x, w, d, held, rnd)
    gates = jnp.zeros((x.shape[0], d["E"]), jnp.float32)
    gates = jax.vmap(lambda g, i, v: g.at[i].add(v))(gates, idx, wt)[:, first : first + n]  # [S, n]

    def one(args):
        wg, wu, wd, gate = args
        return gate[:, None] * swiglu(x, {"gate_proj": wg, "up_proj": wu, "down_proj": wd}, rnd)

    y = jnp.sum(jax.lax.map(one, (w["w_gate"], w["w_up"], w["w_down"], gates.T)), axis=0)
    return d["scale"] * y, near


def block(x, w, d, kind, rnd=identity, held=None):
    """One layer: (x [S, D], near [S]); a dense layer's ``near`` is all false."""
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], d["eps"]), w["attn"], d, kind[0], rnd)
    h = rms_norm(x, w["mlp_norm"]["scale"], d["eps"])
    if kind[1] == W.DENSE:
        return x + swiglu(h, w["mlp"], rnd), jnp.zeros((x.shape[0],), bool)
    y, near = routed(h, w["moe"], d, d["held"] if held is None else held, rnd)
    return x + swiglu(h, w["shared"], rnd) + y, near


def mtp_block(x, emb_next, w, d, rnd=identity, held=None):
    """The multi-token-prediction block over a sequence: ``x [S, D]`` the
    main stack's hidden states before its final norm, ``emb_next [S, D]`` the
    embedding of each position's next token. Returns (its final-norm hidden
    [S, D], near [S])."""
    joined = jnp.concatenate(
        [rms_norm(emb_next, w["enorm"]["scale"], d["eps"]), rms_norm(x, w["hnorm"]["scale"], d["eps"])], axis=-1)
    y, near = block(rnd(joined) @ rnd(w["eh_proj"]), w["block"], d, W.MTP_KIND, rnd, held)
    return rms_norm(y, w["final_norm"]["scale"], d["eps"]), near


# ---- the weights as the serving configuration states them ----


def stated(tree):
    """Matrices were made in bfloat16 (the configuration's weights); widen
    them. Norm scales and the selection bias are float32 already."""
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def make_forward(d, rnd=identity):
    """``forward(key, tokens [S], at=None, with_near=False, with_draft=False)
    -> logits [S or len(at), V]`` of one sequence, a layer at a time. Asked,
    also where any sparse layer's selection (of the main stack) was near its
    edge, at the same positions; and, asked, the block's logits followed
    teacher-forced (``draft[i]`` predicts ``tokens[at[i] + 2]``; the
    sequence's last position has no next token and its draft means nothing)
    with the block's own ``near``. The key is an argument of each program,
    never a constant of it: every seed runs the same compiled programs out
    of the persistent cache."""

    @jax.jit
    def embed(key, toks):
        return stated(W.make_outer(d, key, jnp.bfloat16, only=("embed",)))["embed"]["embedding"][toks]

    @functools.partial(jax.jit, static_argnums=(3,))
    def layer(key, x, l, kind):
        return block(x, stated(W.make_layer(d, key, l, kind, jnp.bfloat16)), d, kind, rnd)

    @jax.jit
    def mtp(key, x, emb_next):
        return mtp_block(x, emb_next, stated(W.make_mtp(d, key, jnp.bfloat16)), d, rnd)

    @jax.jit
    def head(key, x, at):
        outer = stated(W.make_outer(d, key, jnp.bfloat16, only=("final_norm", "lm_head")))
        h = rms_norm(x, outer["final_norm"]["scale"], d["eps"])
        return rnd(h[at]) @ rnd(outer["lm_head"]["kernel"])

    @jax.jit
    def draft_head(key, y, at):
        outer = stated(W.make_outer(d, key, jnp.bfloat16, only=("lm_head",)))
        return rnd(y[at]) @ rnd(outer["lm_head"]["kernel"])

    def forward(key, tokens, at=None, with_near=False, with_draft=False):
        emb = embed(key, tokens)
        x, near = emb, False
        for l, kind in enumerate(d["kinds"]):
            x, near_l = layer(key, x, jnp.int32(l), kind)
            near = near | near_l
        at = jnp.arange(tokens.shape[0]) if at is None else at
        out = (head(key, x, at),) + ((near[at],) if with_near else ())
        if with_draft:
            y, near_mtp = mtp(key, x, jnp.concatenate([emb[1:], emb[:1]], axis=0))
            out += (draft_head(key, y, at), near_mtp[at])
        return out if len(out) > 1 else out[0]

    return forward


# ---- serving: the gap of each served token ----


def serve_gaps(d, key, tokens, first, count, width, *, control=False):
    """``tokens [n, T]``: each row a prompt followed by its served tokens,
    padded; served token ``i`` of row ``r`` is predicted at position
    ``first[r] + i`` for ``i < count[r]``. One row at a time. Returns
    arrays ``[n, width]`` with the masks ``valid`` (served positions that are
    compared) and ``near`` (served positions left out: a held expert within
    ``EDGE`` of the selection's edge in some layer): ``gap`` = the reference's
    best logit minus the served token's logit; ``agree`` = the served token
    is the reference's own first choice; ``drafted`` (served tokens that had a
    draft: all but a request's first) and ``draft_agree`` (of them, the
    block's first choice two positions back IS the served token); and, with
    ``control``, ``control_gap`` = the gap of the token that the reference
    with float8 (e4m3) operands in every product puts first."""
    n, T = tokens.shape
    idx = jnp.minimum(first[:, None] + jnp.arange(width)[None, :], T - 1)
    served_here = jnp.arange(width)[None, :] < count[:, None]
    served = jnp.take_along_axis(tokens, jnp.minimum(idx + 1, T - 1), axis=1)
    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap, agree, control_gap, near, draft_agree = [], [], [], [], []
    sound = make_forward(d)
    lower = make_forward(d, rnd=fp8_round) if control else None
    with highest():
        for r in range(n):
            # The block's logits one position back of each served token's own: they predict that token.
            ref, near_r, draft, _ = sound(key, tokens[r], jnp.concatenate([idx[r], jnp.maximum(idx[r] - 1, 0)]),
                                          with_near=True, with_draft=True)
            ref, near_r, draft = ref[:width], near_r[:width], draft[width:]
            near.append(near_r)
            best = jnp.max(ref, axis=-1)
            gap.append(best - pick(ref, served[r]))
            agree.append(jnp.argmax(ref, axis=-1) == served[r])
            draft_agree.append(jnp.argmax(draft, axis=-1) == served[r])
            if control:
                low = jnp.argmax(lower(key, tokens[r], idx[r]), axis=-1)
                control_gap.append(best - pick(ref, low))
    near = jnp.stack(near)
    out = {"valid": served_here & ~near, "near": served_here & near, "gap": jnp.stack(gap), "agree": jnp.stack(agree),
           "drafted": served_here & (jnp.arange(width)[None, :] >= 1), "draft_agree": jnp.stack(draft_agree)}
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
    valid, near, drafted = np.asarray(res["valid"]), np.asarray(res["near"]), np.asarray(res["drafted"])
    gaps = np.asarray(res["gap"])[valid].tolist()
    if not gaps:
        raise SystemExit("every served position lies near a selection's edge: nothing to compare")
    out = {"requests": len(reqs), "positions": len(gaps), "agree": int(np.asarray(res["agree"])[valid].sum()),
           "gap_max": max(gaps), "gap_mean": sum(gaps) / len(gaps),
           # for the record, not compared: the positions left out, and the gap with them in
           "positions_near_edge": int(near.sum()), "gap_max_all_positions": float(np.asarray(res["gap"])[valid | near].max()),
           # the block followed teacher-forced: what the engine's mtp_accept_pct should read
           "draft_positions": int(drafted.sum()),
           "draft_agree_pct": 100.0 * float(np.asarray(res["draft_agree"])[drafted].sum()) / max(1, int(drafted.sum()))}
    if control:
        cgaps = np.asarray(res["control_gap"])[valid].tolist()
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    print(f"the block followed teacher-forced: draft_agree_pct {out['draft_agree_pct']:.2f} over "
          f"{out['draft_positions']} served tokens that had a draft", flush=True)
    return out


def train_check(check: dict, control: bool) -> dict:
    raise SystemExit("the exaone_moe family is served, not trained: it has no training reference")
