"""Seeded weights of the ``jamba`` family, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes. Every leaf is drawn from a key folded from the run's key,
the leaf's path and the layer index.

Layout is the program's (``models/jamba.py``): ``embed/embedding [V, D]``
(also the head), ``final_norm/scale``, and a layer's ``norm1/scale``,
``norm2/scale``, ``mlp/{gate_up [D, 2F] = [g | u], down [F, D]}`` and, by the
layer's kind, ``ssm/{in_proj [D, 2 di] = [u | z], conv_w [K, di], conv_b
[di], x_proj [di, R + 2N] = [delta | B | C], dt_norm [R], b_norm [N], c_norm
[N], dt_proj [R, di], dt_bias [di], A_log [N, di], D [di], out_proj [di, D]}``
or ``attn/{q_proj [D, H, dh], k_proj [D, Hk, dh], v_proj [D, Hk, dh], o_proj
[H dh, D]}``. Matrices are normal with variance 1/fan_in (the convolution's
taps and bias 1/K) in the serving dtype, those that write to the residual
stream times ``residual_out_scale``; the layer norms' scales are ones; the
three inner norms' scales are ``1 + inner_norm_std x N(0, 1)`` (exactly 1
would leave them untested); ``A_log[n, c] = log(n + 1)``, ``dt_bias`` the
inverse softplus of a step log-uniform in [``dt_min``, ``dt_max``], ``D``
ones, ``dt_proj`` uniform in ``+- R^-1/2``: the published Mamba
initialisation, so the decays ``exp(dt A)`` span what a trained model's do.
The configuration file states the sizes under ``seeded_values``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest

from .shape import FULL, MAMBA, dims  # noqa: F401 (dims is this file's by the family's contract)

INNER_NORMS = ("dt_norm", "b_norm", "c_norm")


def layer_leaves(d: dict, kind: str) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves that are plain
    draws; fan_in None = ones. ``A_log``, ``dt_bias``, ``dt_proj``, ``D`` and
    the inner norms' scales are made in ``make_layer``."""
    D, F = d["D"], d["F"]
    res = d["seeded"]["residual_out_scale"] ** -2
    out = {
        ("norm1", "scale"): ((D,), None), ("norm2", "scale"): ((D,), None),
        ("mlp", "gate_up"): ((D, 2 * F), D), ("mlp", "down"): ((F, D), F * res),
    }
    if kind == MAMBA:
        di, N, K, R = d["di"], d["N"], d["K"], d["R"]
        out.update({
            ("ssm", "in_proj"): ((D, 2 * di), D), ("ssm", "conv_w"): ((K, di), K), ("ssm", "conv_b"): ((di,), K),
            ("ssm", "x_proj"): ((di, R + 2 * N), di), ("ssm", "out_proj"): ((di, D), di * res),
        })
    else:
        H, Hk, dh = d["H"], d["Hk"], d["dh"]
        out.update({
            ("attn", "q_proj"): ((D, H, dh), D), ("attn", "k_proj"): ((D, Hk, dh), D),
            ("attn", "v_proj"): ((D, Hk, dh), D), ("attn", "o_proj"): ((H * dh, D), H * dh * res),
        })
    return out


def is_matrix(path, shape) -> bool:
    return len(shape) >= 2 and path[-1] != "conv_w"


def make_layer(d: dict, key, layer, kind: str, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s leaves (nested dict) for a traced or concrete
    index; matrices in ``dtype``, the small leaves float32."""
    at = lambda path: jax.random.fold_in(leaf_key(key, ("layers",) + path), layer)
    flat = {path: draw(at(path), shape, fan_in, dtype if is_matrix(path, shape) else jnp.float32)
            for path, (shape, fan_in) in layer_leaves(d, kind).items()}
    if kind == MAMBA:
        di, N, R, s = d["di"], d["N"], d["R"], d["seeded"]
        step = jnp.exp(jax.random.uniform(at(("ssm", "dt_bias")), (di,), jnp.float32,
                                          math.log(s["dt_min"]), math.log(s["dt_max"])))
        flat[("ssm", "dt_bias")] = step + jnp.log(-jnp.expm1(-step))
        flat[("ssm", "dt_proj")] = jax.random.uniform(
            at(("ssm", "dt_proj")), (R, di), jnp.float32, -(R ** -0.5), R ** -0.5).astype(dtype)
        flat[("ssm", "A_log")] = jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (N, di))
        flat[("ssm", "D")] = jnp.ones((di,), jnp.float32)
        for name, n in zip(INNER_NORMS, (R, N, N)):
            flat[("ssm", name)] = 1.0 + s["inner_norm_std"] * jax.random.normal(at(("ssm", name)), (n,), jnp.float32)
    return nest(flat)


def outer_leaves(d: dict) -> dict:
    return {("embed", "embedding"): ((d["V"], d["D"]), d["D"]), ("final_norm", "scale"): ((d["D"],), None)}


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
