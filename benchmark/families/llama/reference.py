"""The plain reference of the ``llama`` family: a Llama-shaped block
(RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU) in
straightforward float32 ``jax.numpy``, one file for every configuration of
the family.

It imports nothing of the program under test and takes nothing the
program made: weights come from the family's ``weights.py`` and the seed,
one layer at a time, so a model whose float32 weights exceed the chip still
fits. Matrix products run at ``jax.default_matmul_precision("highest")``;
callers wrap their calls in :func:`highest`.

Two drivers sit on the block:

- :func:`serve_gaps` — a teacher-forced forward over prompt + served
  tokens with the weights quantised as the configuration states, and the
  gap by which each served token's logit lies below the reference's best;
- :func:`train_steps` — loss, per-leaf gradient norms and per-leaf
  parameter change over the first steps, with its own Adafactor.

``lower`` selects the control: the same mathematics with its matrix
products' operands rounded to the next precision down.

:func:`serve_check` and :func:`train_check` are what ``reference_run.py``
calls (the contract is stated there): they read the harness's
``check_in.json`` and hand its sizes to the two drivers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.families._common import (  # noqa: F401  (``highest`` is what callers wrap their calls in)
    adafactor_scaled, bf16, fake_int, fp8_round, highest, identity, rms_norm, zero_stats,
)

from . import weights as W


# ---- the block ----


def rope(x, positions, theta):
    """x [B, S, heads, hd], positions [B, S]; rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, d, rnd=identity):
    """Causal grouped-query attention; one KV head's group at a time so the
    [S, S] scores of a long sequence stay small."""
    B, S, _ = x.shape
    H, K, hd = d["H"], d["K"], d["hd"]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = rnd(x)
    q = jnp.einsum("bsd,dhe->bshe", x, rnd(w["q_proj"]["kernel"]))
    k = jnp.einsum("bsd,dke->bske", x, rnd(w["k_proj"]["kernel"]))
    v = jnp.einsum("bsd,dke->bske", x, rnd(w["v_proj"]["kernel"]))
    q, k = rope(q, pos, d["theta"]), rope(k, pos, d["theta"])
    q = q.reshape(B, S, K, H // K, hd)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def group(args):
        qg, kg, vg = args  # [B, S, G, hd], [B, S, hd], [B, S, hd]
        s = jnp.einsum("bsge,bte->bgst", rnd(qg), rnd(kg)) / jnp.sqrt(float(hd))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgst,bte->bsge", rnd(p), rnd(vg))

    # Recomputed in the backward pass: only one group's [S, S] scores live at a time.
    out = jax.lax.map(
        jax.checkpoint(group), (q.transpose(2, 0, 1, 3, 4), k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3))
    )  # [K, B, S, G, hd]
    out = out.transpose(1, 2, 0, 3, 4).reshape(B, S, H * hd)
    return rnd(out) @ rnd(w["o_proj"]["kernel"])


def mlp(x, w, rnd=identity):
    x = rnd(x)
    h = jax.nn.silu(x @ rnd(w["gate_proj"]["kernel"])) * (x @ rnd(w["up_proj"]["kernel"]))
    return rnd(h) @ rnd(w["down_proj"]["kernel"])


def block(x, w, d, rnd=identity):
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], d["eps"]), w["attn"], d, rnd)
    return x + mlp(rms_norm(x, w["mlp_norm"]["scale"], d["eps"]), w["mlp"], rnd)


# ---- the weights as the serving configuration states them ----


def quantise_layer(w, levels):
    out = jax.tree.map(lambda a: a, w)
    for part in ("attn", "mlp"):
        for name, leaf in w[part].items():
            out[part][name] = {"kernel": fake_int(leaf["kernel"], 0, levels)}
    return out


# ---- serving: the gap of each served token ----


def serve_gaps(d, key, tokens, first, count, width, *, levels=127, control_levels=None):
    """``tokens [n, T]``: each row a prompt followed by its served tokens,
    padded; served token ``i`` of row ``r`` is predicted at position
    ``first[r] + i`` for ``i < count[r]``. One row at a time (the longest
    decides the memory, not the sample). Returns arrays ``[n, width]``
    with mask ``valid``: ``gap`` = the reference's best logit minus the
    served token's logit; ``agree`` = the served token is the reference's
    own first choice; and, with ``control_levels``, ``control_gap`` = the
    gap of the token that the lower-precision weights put first."""
    n, T = tokens.shape
    idx = jnp.minimum(first[:, None] + jnp.arange(width)[None, :], T - 1)  # [n, width]
    valid = jnp.arange(width)[None, :] < count[:, None]
    served = jnp.take_along_axis(tokens, jnp.minimum(idx + 1, T - 1), axis=1)

    # The key is an argument, never a constant of the program: every seed
    # then runs the same compiled programs out of the persistent cache.
    @functools.partial(jax.jit, static_argnums=(2,))
    def embed(key_, row, levels_):
        table = W.make_outer(d, key_, only=("embed",))["embed"]["embedding"]
        return fake_int(table, -1, levels_)[row]

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(1,))
    def layer(key_, x, l, levels_):
        return block(x, quantise_layer(W.make_layer(d, key_, l), levels_), d)

    @functools.partial(jax.jit, static_argnums=(3,))
    def logits(key_, x, at, levels_):
        outer = W.make_outer(d, key_, only=("final_norm", "lm_head"))
        h = rms_norm(x, outer["final_norm"]["scale"], d["eps"])
        h = jnp.take_along_axis(h, at[:, :, None], axis=1)  # [1, width, D]
        return h @ fake_int(outer["lm_head"]["kernel"], 0, levels_)

    def forward(r, levels_):
        x = embed(key, tokens[r : r + 1], levels_)
        for l in range(d["L"]):
            x = layer(key, x, jnp.int32(l), levels_)
        return logits(key, x, idx[r : r + 1], levels_)[0]  # [width, V]

    pick = lambda lg, tok: jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
    gap, agree, control_gap = [], [], []
    with highest():
        for r in range(n):
            ref = forward(r, levels)
            best = jnp.max(ref, axis=-1)
            gap.append(best - pick(ref, served[r]))
            agree.append(jnp.argmax(ref, axis=-1) == served[r])
            if control_levels is not None:
                low = jnp.argmax(forward(r, control_levels), axis=-1)
                control_gap.append(best - pick(ref, low))
    out = {"valid": valid, "gap": jnp.stack(gap), "agree": jnp.stack(agree)}
    if control_gap:
        out["control_gap"] = jnp.stack(control_gap)
    return out


# ---- training: loss, gradient norms, parameter change ----


def train_steps(d, key, batches, *, lr, lower=False):
    """Follow the trainer's first ``len(batches)`` steps on ``batches[i]
    [B, S]``: next-token cross-entropy, Adafactor at learning rate ``lr``
    (update clipped to unit root mean square per stacked leaf and scaled by
    the leaf's own root mean square, floor 1e-3), parameters held in
    bfloat16 as the configuration states. Returns ``losses``, ``grad_norm``
    (first step, per stacked leaf, as the optimizer gets it) and
    ``delta_norm`` (parameter change over all the steps, per stacked leaf).

    One layer at a time: a forward pass that keeps each layer's input, a
    backward pass for the per-leaf statistics (the clip needs the whole
    stacked leaf), and a second backward pass that applies the update.
    """
    rnd = fp8_round if lower else identity
    L = d["L"]
    # Parameters are held as the configuration states them, in bfloat16 (the
    # update rounds to it, so nothing is lost), and widened where used. The
    # key is an argument of each program, so every seed runs the same ones.
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    start_layer = jax.jit(lambda k, l: W.make_layer(d, k, l, jnp.bfloat16))
    layers = [start_layer(key, jnp.int32(l)) for l in range(L)]
    outer = start_outer = jax.jit(lambda k: W.make_outer(d, k, jnp.bfloat16))(key)
    stats_l = [jax.tree.map(lambda a: zero_stats(a.shape), w) for w in layers]
    stats_o = jax.tree.map(lambda a: zero_stats(a.shape), outer)
    is_stats = lambda n: isinstance(n, dict) and ("v" in n or "row" in n)

    fwd = jax.jit(lambda x, w: block(x, f32(w), d, rnd))

    def head_loss(x, final_scale, head, tokens):
        h = rms_norm(x, final_scale, d["eps"])[:, :-1]
        logits = rnd(h) @ rnd(head)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    head_vjp = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1, 2)))

    @jax.jit
    def layer_grads(x, w, dx):
        _, vjp = jax.vjp(lambda x_, w_: block(x_, w_, d, rnd), x, f32(w))
        return vjp(dx)  # (dx_in, dw)

    @jax.jit
    def sums(g, v, step):
        """Per-leaf sum of g^2 and of u^2 for one layer's (or the outer) tree."""
        def one(g_, v_):
            u, _ = adafactor_scaled(bf16(g_), v_, step)
            return jnp.stack([jnp.sum(bf16(g_) ** 2), jnp.sum(u * u), jnp.float32(u.size)])
        return jax.tree.map(one, g, v, is_leaf=lambda n: is_stats(n))

    @jax.jit
    def apply(w, g, v, step, clip, scale):
        def one(w_, g_, v_, clip_, scale_):
            u, nv = adafactor_scaled(bf16(g_), v_, step)
            return (w_.astype(jnp.float32) - lr * scale_ * u / clip_).astype(jnp.bfloat16), nv
        pairs = jax.tree.map(one, w, g, v, clip, scale, is_leaf=lambda n: is_stats(n))
        is_pair = lambda n: isinstance(n, tuple)
        return (jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
                jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair))

    sq = jax.jit(lambda w: jax.tree.map(
        lambda a: jnp.stack([jnp.sum(a.astype(jnp.float32) ** 2), jnp.float32(a.size)]), w))
    dsq = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sum((x.astype(jnp.float32) - y.astype(jnp.float32)) ** 2), a, b))
    add = lambda a, b: jax.tree.map(jnp.add, a, b)

    losses, grad_norm = [], None
    with highest():
        for step, tokens in enumerate(batches):
            tokens = jnp.asarray(tokens, jnp.int32)
            t = jnp.float32(step)
            xs = [outer["embed"]["embedding"].astype(jnp.float32)[tokens]]
            for w in layers:
                xs.append(fwd(xs[-1], w))
            loss, (dx_top, d_final, d_head) = head_vjp(
                xs[-1], outer["final_norm"]["scale"].astype(jnp.float32),
                outer["lm_head"]["kernel"].astype(jnp.float32), tokens
            )
            losses.append(float(loss))

            def outer_grads(dx0):
                return {
                    "embed": {"embedding": jnp.zeros(outer["embed"]["embedding"].shape, jnp.float32).at[tokens].add(dx0)},
                    "final_norm": {"scale": d_final},
                    "lm_head": {"kernel": d_head},
                }

            # First backward pass: what the clip and the norms need.
            dx, layer_sums, psq = dx_top, None, None
            for l in reversed(range(L)):
                dx, dw = layer_grads(xs[l], layers[l], dx)
                s = sums(dw, stats_l[l], t)
                layer_sums = s if layer_sums is None else add(layer_sums, s)
                p = sq(layers[l])
                psq = p if psq is None else add(psq, p)
            g_outer = outer_grads(dx)
            outer_sums, outer_psq = sums(g_outer, stats_o, t), sq(outer)
            if step == 0:
                grad_norm = {
                    "layers": jax.tree.map(lambda s: float(jnp.sqrt(s[0])), layer_sums),
                    **jax.tree.map(lambda s: float(jnp.sqrt(s[0])), outer_sums),
                }
            clip_of = lambda s: jnp.maximum(1.0, jnp.sqrt(s[1] / s[2]))
            scale_of = lambda p: jnp.maximum(1e-3, jnp.sqrt(p[0] / p[1]))
            clip_l, scale_l = jax.tree.map(clip_of, layer_sums), jax.tree.map(scale_of, psq)
            # Second backward pass: the update, layer by layer.
            dx = dx_top
            for l in reversed(range(L)):
                dx, dw = layer_grads(xs[l], layers[l], dx)
                layers[l], stats_l[l] = apply(layers[l], dw, stats_l[l], t, clip_l, scale_l)
            outer, stats_o = apply(
                outer, g_outer, stats_o, t,
                jax.tree.map(clip_of, outer_sums), jax.tree.map(scale_of, outer_psq),
            )
            del xs
        delta = None
        for l in range(L):
            s = dsq(layers[l], start_layer(key, jnp.int32(l)))
            delta = s if delta is None else add(delta, s)
        delta_norm = {
            "layers": jax.tree.map(lambda s: float(jnp.sqrt(s)), delta),
            **jax.tree.map(lambda s: float(jnp.sqrt(s)), dsq(outer, start_outer)),
        }
    return {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta_norm}


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
        d, jax.random.key(check["seed"]), jnp.asarray(tokens), jnp.asarray(first),
        jnp.asarray(count), int(check["width"]), control_levels=7 if control else None,
    )
    valid = np.asarray(res["valid"])
    gaps = np.asarray(res["gap"])[valid].tolist()
    agree = int(np.asarray(res["agree"])[valid].sum())
    cgaps = np.asarray(res["control_gap"])[valid].tolist() if control else []
    out = {}
    out.update(requests=len(reqs), positions=len(gaps), agree=agree, gap_max=max(gaps),
               gap_mean=sum(gaps) / len(gaps))
    if control:
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    return out


def train_check(check: dict, control: bool) -> dict:
    from benchmark.entry_train import seeded_batch

    d = W.dims(check["config"])
    batches = [seeded_batch(check["seed"], s, check["batch"], check["seq_len"], d["V"])
               for s in range(check["steps"])]
    key = jax.random.key(check["seed"])
    out = train_steps(d, key, batches, lr=check["lr"])
    if control:
        out["control"] = train_steps(d, key, batches, lr=check["lr"], lower=True)
    return out
