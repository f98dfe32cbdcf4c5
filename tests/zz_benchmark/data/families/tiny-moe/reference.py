"""The plain reference of the made-up ``tiny-moe`` family: rotate-half RoPE,
grouped-query causal attention, and a layer of GELU experts with a top-k
router whose gates are a softmax over the k chosen, in float32
``jax.numpy``. It imports nothing of the program and nothing of another
family: what no block's shape decides (RMSNorm, the plain Adafactor, the
precision) comes from ``benchmark/families/_common.py``. At this size the
whole model fits, so a step is one ``jax.value_and_grad`` over the stacked
tree, and Adafactor runs on the stacked leaves as the trainer's does.

Only training is followed: ``serve_check`` says so. A configuration file
whose ``bench.reference_keeps`` is a number has this reference's router keep
that many experts a token, whatever the file's ``num_experts_per_tok`` (which
the program runs): the fault the rehearsal's second cell is there to show.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families._common import adafactor_scaled, bf16, highest, rms_norm, zero_stats

from . import weights as W


def rope(x, theta):
    """x [B, S, heads, hd]; rotate-half convention, positions 0..S-1."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, w, d):
    B, S, _ = x.shape
    H, K, hd = d["H"], d["K"], d["hd"]
    q = rope(jnp.einsum("bsd,dhe->bshe", x, w["q_proj"]["kernel"]), d["theta"]).reshape(B, S, K, H // K, hd)
    k = rope(jnp.einsum("bsd,dke->bske", x, w["k_proj"]["kernel"]), d["theta"])
    v = jnp.einsum("bsd,dke->bske", x, w["v_proj"]["kernel"])
    s = jnp.einsum("bskge,btke->bkgst", q, k) / jnp.sqrt(float(hd))
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgst,btke->bskge", p, v).reshape(B, S, H * hd) @ w["o_proj"]["kernel"]


def experts(x, w, k):
    """x [B, S, D]. Every expert computes every token (dense dispatch); a
    token keeps its k best experts' outputs, weighted by a softmax over
    those k router logits."""
    logits = x @ w["gate"]
    top, idx = jax.lax.top_k(logits, k)
    gates = jnp.sum(jax.nn.one_hot(idx, logits.shape[-1]) * jax.nn.softmax(top, axis=-1)[..., None], axis=-2)
    h = jax.nn.gelu(jnp.einsum("bsd,edf->ebsf", x, w["w_in"]))
    return jnp.einsum("ebsd,bse->bsd", jnp.einsum("ebsf,efd->ebsd", h, w["w_out"]), gates)


def block(x, w, d):
    x = x + attention(rms_norm(x, w["attn_norm"]["scale"], d["eps"]), w["attn"], d)
    return x + experts(rms_norm(x, w["mlp_norm"]["scale"], d["eps"]), w["moe_mlp"], d["k"])


def loss_of(params, tokens, d):
    x = params["embed"]["embedding"][tokens]
    for l in range(d["L"]):
        x = block(x, jax.tree.map(lambda a: a[l], params["layers"]), d)
    h = rms_norm(x, params["final_norm"]["scale"], d["eps"])[:, :-1]
    logp = jax.nn.log_softmax(h @ params["lm_head"]["kernel"], axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def train_check(check: dict, control: bool) -> dict:
    from benchmark.entry_train import seeded_batch

    d, lr = W.dims(check["config"]), check["lr"]
    d["k"] = int(check["config"]["bench"].get("reference_keeps", d["k"]))
    key = jax.random.key(check["seed"])
    start = params = W.make_params(d, key, jnp.bfloat16)
    stats = jax.tree.map(lambda a: zero_stats(a.shape), params)
    f32 = lambda tree: jax.tree.map(lambda a: a.astype(jnp.float32), tree)
    rms = lambda a: jnp.sqrt(jnp.mean(a * a))
    losses, grad_norm = [], None
    with highest():
        for step in range(check["steps"]):
            tokens = jnp.asarray(seeded_batch(check["seed"], step, check["batch"], check["seq_len"], d["V"]))
            loss, grads = jax.value_and_grad(loss_of)(f32(params), tokens, d)
            losses.append(float(loss))
            grads = jax.tree.map(bf16, grads)
            if step == 0:
                grad_norm = jax.tree.map(lambda g: float(jnp.sqrt(jnp.sum(g * g))), grads)

            def update(p, g, v):
                u, nv = adafactor_scaled(g, v, jnp.float32(step))
                p32 = p.astype(jnp.float32)
                return (p32 - lr * jnp.maximum(1e-3, rms(p32)) * u / jnp.maximum(1.0, rms(u))).astype(jnp.bfloat16), nv

            pairs = jax.tree.map(update, params, grads, stats)
            is_pair = lambda n: isinstance(n, tuple)
            params = jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair)
            stats = jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair)
        delta_norm = jax.tree.map(lambda a, b: float(jnp.sqrt(jnp.sum((a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2))),
                                  params, start)
    return {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta_norm}


def serve_check(check: dict, control: bool) -> dict:
    raise SystemExit("the tiny-moe family is made up for a training cell; it has no serving reference")
