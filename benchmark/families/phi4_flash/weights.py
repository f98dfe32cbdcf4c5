"""Seeded weights of the ``phi4_flash`` family, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes. Every leaf is drawn from a key folded from the run's key,
the leaf's path and the layer index.

Layout is the program's (``models/phi4_flash.py``): ``embed/embedding [V,
D]`` (also the head), ``final_norm/{scale, bias}``, and a layer's
``norm1``, ``norm2`` ``{scale, bias}``, ``mlp/{gate_up [D, 2F] = [g | u],
down [F, D]}`` and, by the layer's kind, ``ssm/{in_proj [D, 2 di] = [u | z],
conv_w [K, di], conv_b [di], x_proj [di, R + 2N] = [delta | B | C], dt_proj
[R, di], dt_bias [di], A_log [N, di], D [di], out_proj [di, D]}``,
``attn/{q_proj [D, D], q_bias, kv_proj [D, 2 Hk dh] = [k | v], kv_bias (a
cross layer has neither), o_proj [D, D], o_bias, lambda_q1, lambda_k1,
lambda_q2, lambda_k2 [dh], subln [2 dh]}`` or ``gmu/{in_proj [D, di],
out_proj [di, D]}``. Matrices are normal with variance 1/fan_in (the
convolution's taps and bias 1/K) in the serving dtype, those that write to
the residual stream times ``residual_out_scale``; norm scales are ones;
every bias is ``bias_std x N(0, 1)`` and the four lambda vectors
``lambda_std x N(0, 1)`` (zero would leave them untested); ``A_log[n, c] =
log(n + 1)``, ``dt_bias`` the inverse softplus of a step log-uniform in
[``dt_min``, ``dt_max``], ``D`` ones, ``dt_proj`` uniform in ``+- R^-1/2``:
the published Mamba initialisation, so the decays ``exp(dt A)`` span what a
trained model's do. The configuration file states the sizes under
``seeded_values``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest

from .shape import CROSS, GMU, MAMBA, MAMBA_MEMORY, dims  # noqa: F401 (dims is this file's by the family's contract)

ONES = {"scale", "subln"}
LAMBDAS = tuple(f"lambda_{n}" for n in ("q1", "k1", "q2", "k2"))


def layer_leaves(d: dict, kind: str) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves that are plain
    draws; fan_in None = ones. A small leaf (a bias, a lambda vector) has
    the fan_in that gives its stated deviation. ``A_log``, ``dt_bias``,
    ``dt_proj`` and ``D`` are made in ``make_layer``."""
    D, F, s = d["D"], d["F"], d["seeded"]
    res, bias, lam = s["residual_out_scale"] ** -2, s["bias_std"] ** -2, s["lambda_std"] ** -2
    out = {
        ("norm1", "scale"): ((D,), None), ("norm1", "bias"): ((D,), bias),
        ("norm2", "scale"): ((D,), None), ("norm2", "bias"): ((D,), bias),
        ("mlp", "gate_up"): ((D, 2 * F), D), ("mlp", "down"): ((F, D), F * res),
    }
    if kind in (MAMBA, MAMBA_MEMORY):
        di, N, K, R = d["di"], d["N"], d["K"], d["R"]
        out.update({
            ("ssm", "in_proj"): ((D, 2 * di), D), ("ssm", "conv_w"): ((K, di), K), ("ssm", "conv_b"): ((di,), K),
            ("ssm", "x_proj"): ((di, R + 2 * N), di), ("ssm", "out_proj"): ((di, D), di * res),
        })
    elif kind == GMU:
        out.update({("gmu", "in_proj"): ((D, d["di"]), D), ("gmu", "out_proj"): ((d["di"], D), d["di"] * res)})
    else:
        dh = d["dh"]
        out.update({
            ("attn", "q_proj"): ((D, D), D), ("attn", "q_bias"): ((D,), bias),
            ("attn", "o_proj"): ((D, D), D * res), ("attn", "o_bias"): ((D,), bias),
            ("attn", "subln"): ((2 * dh,), None), **{("attn", n): ((dh,), lam) for n in LAMBDAS},
        })
        if kind != CROSS:
            kv = 2 * d["Hk"] * dh
            out.update({("attn", "kv_proj"): ((D, kv), D), ("attn", "kv_bias"): ((kv,), bias)})
    return out


def is_matrix(path, shape) -> bool:
    return len(shape) == 2 and path[-1] not in ("conv_w",)


def make_layer(d: dict, key, layer, kind: str, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s leaves (nested dict) for a traced or concrete
    index; matrices in ``dtype``, the small leaves float32."""
    at = lambda path: jax.random.fold_in(leaf_key(key, ("layers",) + path), layer)
    flat = {path: draw(at(path), shape, fan_in, dtype if is_matrix(path, shape) else jnp.float32)
            for path, (shape, fan_in) in layer_leaves(d, kind).items()}
    if kind in (MAMBA, MAMBA_MEMORY):
        di, N, R, s = d["di"], d["N"], d["R"], d["seeded"]
        step = jnp.exp(jax.random.uniform(at(("ssm", "dt_bias")), (di,), jnp.float32,
                                          math.log(s["dt_min"]), math.log(s["dt_max"])))
        flat[("ssm", "dt_bias")] = step + jnp.log(-jnp.expm1(-step))
        flat[("ssm", "dt_proj")] = jax.random.uniform(
            at(("ssm", "dt_proj")), (R, di), jnp.float32, -(R ** -0.5), R ** -0.5).astype(dtype)
        flat[("ssm", "A_log")] = jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (N, di))
        flat[("ssm", "D")] = jnp.ones((di,), jnp.float32)
    return nest(flat)


def outer_leaves(d: dict) -> dict:
    return {
        ("embed", "embedding"): ((d["V"], d["D"]), d["D"]),
        ("final_norm", "scale"): ((d["D"],), None),
        ("final_norm", "bias"): ((d["D"],), d["seeded"]["bias_std"] ** -2),
    }


def make_outer(d: dict, key, dtype=jnp.float32, only=None) -> dict:
    return nest({
        path: draw(leaf_key(key, path), shape, fan_in, dtype if len(shape) == 2 else jnp.float32)
        for path, (shape, fan_in) in outer_leaves(d).items()
        if only is None or path[0] in only
    })


def make_params(d: dict, key, dtype=jnp.float32) -> dict:
    """The whole tree as the program holds it: the layers a list, nothing
    stacked (their shapes differ)."""
    tree = make_outer(d, key, dtype)
    tree["layers"] = [make_layer(d, key, jnp.int32(l), kind, dtype) for l, kind in enumerate(d["kinds"])]
    return tree
