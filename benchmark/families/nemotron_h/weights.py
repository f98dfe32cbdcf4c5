"""Seeded weights of the ``nemotron_h`` family, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes. Every leaf is drawn from a key folded from the run's key,
the leaf's path and the layer index, and an expert's two matrices also from
the expert's id in the WHOLE layer: a chip's share (``experts_held``) and
the uncut layer hold the same numbers for the same expert, which is what
lets the shares add up to the whole.

Layout is the program's (``models/nemotron_h.py``): ``embed/embedding [V,
D]``, ``layers[l]/norm/scale`` and, by the layer's kind, ``ssm/{in_proj [D,
di + C + H], conv_w [K, C], conv_b [C], A_log, dt_bias, D [H], norm_scale
[di], out_proj [di, D]}``, ``attn/{q_proj [D, H, dh], k_proj, v_proj [D, Hk,
dh], o_proj [H dh, D]}`` or ``moe/{router [D, E], e_bias [E], w_up [n, D,
Fe], w_down [n, Fe, D]}`` beside ``shared/{up_proj [D, Fs], down_proj [Fs,
D]}``; ``final_norm/scale``, ``lm_head/kernel [D, V]``. Matrices are normal
with variance 1/fan_in (the embedding 1; the convolution's taps and bias
1/K) in the serving dtype; the small leaves are float32. ``A_log`` is log
U[1, 16], ``dt_bias`` the inverse softplus of a step log-uniform in
[time_step_min, time_step_max] floored at time_step_floor, ``D`` ones: the
published initialisation, so the decays ``exp(dt A)`` span what a trained
model's do. The selection bias is ``e_bias_std x N(0, 1)`` at the size the
configuration file states under ``seeded_values``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest

from .shape import ATTENTION, EXPERTS, MAMBA, dims  # noqa: F401 (dims is this file's by the family's contract)

SMALL = {"scale", "conv_w", "conv_b", "norm_scale"}  # plain draws kept float32


def layer_leaves(d: dict, kind: str) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves that are plain
    draws; fan_in None = a norm scale (ones); a matrix that writes to the
    residual stream has its fan_in divided by the square of
    ``residual_out_scale``, which scales the draw by it. The experts'
    matrices, the selection bias, ``A_log``, ``dt_bias`` and ``D`` are made
    in ``make_layer``."""
    D, res = d["D"], d["seeded"]["residual_out_scale"] ** -2
    out = {("norm", "scale"): ((D,), None)}
    if kind == MAMBA:
        out.update({
            ("ssm", "in_proj"): ((D, d["di"] + d["C"] + d["mH"]), D),
            ("ssm", "conv_w"): ((d["K"], d["C"]), d["K"]),
            ("ssm", "conv_b"): ((d["C"],), d["K"]),
            ("ssm", "norm_scale"): ((d["di"],), None),
            ("ssm", "out_proj"): ((d["di"], D), d["di"] * res),
        })
    elif kind == ATTENTION:
        H, Hk, dh = d["H"], d["Hk"], d["dh"]
        out.update({
            ("attn", "q_proj"): ((D, H, dh), D), ("attn", "k_proj"): ((D, Hk, dh), D),
            ("attn", "v_proj"): ((D, Hk, dh), D), ("attn", "o_proj"): ((H * dh, D), H * dh * res),
        })
    else:
        out.update({
            ("moe", "router"): ((D, d["E"]), D),
            ("shared", "up_proj"): ((D, d["Fs"]), D), ("shared", "down_proj"): ((d["Fs"], D), d["Fs"] * res),
        })
    return out


def make_layer(d: dict, key, layer, kind: str, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s leaves (nested dict) for a traced or concrete
    index; matrices in ``dtype``, the small leaves float32."""
    at = lambda path: jax.random.fold_in(leaf_key(key, ("layers",) + path), layer)
    flat = {path: draw(at(path), shape, fan_in, jnp.float32 if path[-1] in SMALL else dtype)
            for path, (shape, fan_in) in layer_leaves(d, kind).items()}
    if kind == MAMBA:
        H, (lo, hi, floor) = d["mH"], d["dt"]
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            at(("ssm", "dt_bias")), (H,), jnp.float32, math.log(lo), math.log(hi))), floor)
        flat[("ssm", "dt_bias")] = step + jnp.log(-jnp.expm1(-step))
        flat[("ssm", "A_log")] = jnp.log(jax.random.uniform(at(("ssm", "A_log")), (H,), jnp.float32, 1.0, 16.0))
        flat[("ssm", "D")] = jnp.ones((H,), jnp.float32)
    if kind == EXPERTS:
        first, n = d["held"]
        ids = first + jnp.arange(n, dtype=jnp.int32)
        D, Fe = d["D"], d["Fe"]
        for name, shape, fan_in in (("w_up", (D, Fe), D), ("w_down", (Fe, D), Fe * d["seeded"]["residual_out_scale"] ** -2)):
            k = at(("moe", name))
            flat[("moe", name)] = jax.vmap(lambda e: draw(jax.random.fold_in(k, e), shape, fan_in, dtype))(ids)
        flat[("moe", "e_bias")] = d["seeded"]["e_bias_std"] * jax.random.normal(
            at(("moe", "e_bias")), (d["E"],), jnp.float32)
    return nest(flat)


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
    stacked (their shapes differ)."""
    tree = make_outer(d, key, dtype)
    tree["layers"] = [make_layer(d, key, jnp.int32(l), kind, dtype) for l, kind in enumerate(d["kinds"])]
    return tree
