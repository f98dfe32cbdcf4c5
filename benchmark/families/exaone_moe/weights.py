"""Seeded weights of the ``exaone_moe`` family, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes. Every leaf is drawn from a key folded from the run's key,
the leaf's path and the layer index, and a routed expert's three matrices
also from the expert's id in the WHOLE layer: a chip's share
(``experts_held``) and the uncut layer hold the same numbers for the same
expert, which is what lets the shares add up to the whole.

Layout is the program's (``models/mimo_v2.py`` with K-EXAONE's options):
``embed/embedding [V, D]``, ``layers[l]/attn/{q_proj [D, H, dh], k_proj,
v_proj [D, Hk, dh], o_proj [H dh, D], q_norm, k_norm [dh]}``,
``layers[l]/{attn,mlp}_norm/scale``, a dense layer's
``mlp/{gate,up,down}_proj`` or a sparse layer's ``moe/{router [D, E],
e_bias [E], w_gate, w_up [n, D, Fe], w_down [n, Fe, D]}`` beside
``shared/{gate,up,down}_proj``, ``final_norm/scale``, ``lm_head/kernel [D,
V]``, and the multi-token-prediction block ``mtp/{enorm,hnorm,final_norm}/
scale``, ``mtp/eh_proj [2 D, D]``, ``mtp/block/<one sparse full-attention
layer>``.

Matrices are normal with variance 1/fan_in (the embedding 1), and, as the
configuration file's ``assumed.seeded_values`` says and why: every matrix
that WRITES to the residual stream (``o_proj``, every ``down_proj`` and
``w_down``) x ``1 / sqrt(2 x residual_layers)``; the selection bias
``e_bias_std x N(0, 1)``; the q/k norm scales ``qk_norm_scale`` (with ones
a window's softmax averages its keys, attention's output moves no logit and
the served tokens are a function of the last token alone); and ``eh_proj =
[a I ; b I] + N(0, s^2 / 2D)``:
the block starts from ``a`` x the next token's normed embedding + ``b`` x the
main stack's normed hidden state, so that its draft agrees with the main
stack's own choice about every other step (independent draws would agree
once in 19,200 steps, and the path that accepts a draft would never run).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest

from .shape import DENSE, FULL, MOE, MTP_KIND, WINDOW, dims  # noqa: F401 (dims is this file's by the family's contract)

WRITERS = ("o_proj", "down_proj", "w_down")  # the matrices that write to the residual stream


def layer_leaves(d: dict, kind: tuple) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves that are plain
    draws; fan_in None = a norm scale (ones). The routed experts' matrices
    and the selection bias are made in ``make_layer``."""
    D, H, Hk, dh = d["D"], d["H"], d["Hk"], d["dh"]
    out = {
        ("attn", "q_proj"): ((D, H, dh), D),
        ("attn", "k_proj"): ((D, Hk, dh), D),
        ("attn", "v_proj"): ((D, Hk, dh), D),
        ("attn", "o_proj"): ((H * dh, D), H * dh),
        ("attn", "q_norm"): ((dh,), None),
        ("attn", "k_norm"): ((dh,), None),
        ("attn_norm", "scale"): ((D,), None),
        ("mlp_norm", "scale"): ((D,), None),
    }
    width, group = (d["F"], "mlp") if kind[1] == DENSE else (d["Fs"], "shared")
    out.update({(group, "gate_proj"): ((D, width), D), (group, "up_proj"): ((D, width), D),
                (group, "down_proj"): ((width, D), width)})
    if kind[1] == MOE:
        out[("moe", "router")] = ((D, d["E"]), D)
    return out


def _draw(d, key, path, shape, fan_in, dtype):
    """A leaf: a norm scale float32 ones, a matrix in ``dtype``, a writer scaled."""
    if fan_in is None:
        return jnp.ones(shape, jnp.float32)
    w = jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
    return (w * d["residual"] if path[-1] in WRITERS else w).astype(dtype)


def make_layer(d: dict, key, layer, kind: tuple, dtype=jnp.float32, prefix=("layers",), held=None) -> dict:
    """Layer ``layer``'s leaves (nested dict) for a traced or concrete
    index; matrices in ``dtype``, norm scales and the bias float32.
    ``held`` = (first id, count) of the routed experts made (the
    configuration's own share where None)."""
    at = lambda path: jax.random.fold_in(leaf_key(key, prefix + path), layer)
    flat = {path: _draw(d, at(path), path, shape, fan_in, dtype) for path, (shape, fan_in) in layer_leaves(d, kind).items()}
    for name in ("q_norm", "k_norm"):
        flat[("attn", name)] = flat[("attn", name)] * d["seeded"]["qk_norm_scale"]
    if kind[1] == MOE:
        first, n = d["held"] if held is None else held
        ids = first + jnp.arange(n, dtype=jnp.int32)
        D, Fe = d["D"], d["Fe"]
        for name, shape, fan_in in (("w_gate", (D, Fe), D), ("w_up", (D, Fe), D), ("w_down", (Fe, D), Fe)):
            k = at(("moe", name))
            flat[("moe", name)] = jax.vmap(
                lambda e: _draw(d, jax.random.fold_in(k, e), ("moe", name), shape, fan_in, dtype))(ids)
        flat[("moe", "e_bias")] = d["seeded"]["e_bias_std"] * jax.random.normal(
            at(("moe", "e_bias")), (d["E"],), jnp.float32)
    return nest(flat)


def make_mtp(d: dict, key, dtype=jnp.float32, held=None) -> dict:
    """The multi-token-prediction block's leaves: its layer (index 0 under
    its own path), the three norms, and ``eh_proj`` as the module's text says."""
    D, s = d["D"], d["seeded"]
    noise = jax.random.normal(leaf_key(key, ("mtp", "eh_proj")), (2 * D, D), jnp.float32) * (s["mtp_s"] * (2 * D) ** -0.5)
    eye = jnp.eye(D, dtype=jnp.float32)
    ones = lambda: {"scale": jnp.ones((D,), jnp.float32)}  # noqa: E731
    return {
        "enorm": ones(), "hnorm": ones(), "final_norm": ones(),
        "eh_proj": (jnp.concatenate([s["mtp_a"] * eye, s["mtp_b"] * eye], axis=0) + noise).astype(dtype),
        "block": make_layer(d, key, jnp.int32(0), MTP_KIND, dtype, prefix=("mtp", "block"), held=held),
    }


def outer_leaves(d: dict) -> dict:
    return {
        ("embed", "embedding"): ((d["V"], d["D"]), 1),
        ("final_norm", "scale"): ((d["D"],), None),
        ("lm_head", "kernel"): ((d["D"], d["V"]), d["D"]),
    }


def make_outer(d: dict, key, dtype=jnp.float32, only=None) -> dict:
    return nest({
        path: draw(leaf_key(key, path), shape, fan_in, jnp.float32 if fan_in is None else dtype)
        for path, (shape, fan_in) in outer_leaves(d).items()
        if only is None or path[0] in only
    })


def make_params(d: dict, key, dtype=jnp.float32) -> dict:
    """The whole tree as the program holds it: the layers a list, nothing
    stacked (their shapes differ), the block under ``mtp``."""
    tree = make_outer(d, key, dtype)
    tree["layers"] = [make_layer(d, key, jnp.int32(l), kind, dtype) for l, kind in enumerate(d["kinds"])]
    tree["mtp"] = make_mtp(d, key, dtype)
    return tree
