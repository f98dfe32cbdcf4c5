"""Seeded weights of the ``mimo_v2`` family, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes. Every leaf is drawn from a key folded from the run's key,
the leaf's path and the layer index, and an expert's three matrices also
from the expert's id in the WHOLE layer: a chip's share (``experts_held``)
and the uncut layer hold the same numbers for the same expert, which is
what lets the shares add up to the whole.

Layout is the program's (``models/mimo_v2.py``): ``embed/embedding [V, D]``,
``layers[l]/attn/{q_proj [D, H, dqk], k_proj [D, Hk, dqk], v_proj [D, Hk, dv],
o_proj [H dv, D], sink [H]}`` (``sink`` in layers whose kind has one),
``layers[l]/{attn,mlp}_norm/scale``, a dense layer's ``mlp/{gate,up,down}_proj``
or an expert layer's ``moe/{router [D, E], e_bias [E], w_gate, w_up [n, D, F],
w_down [n, F, D]}``, ``final_norm/scale``, ``lm_head/kernel [D, V]``. Matrices
are normal with variance 1/fan_in (the embedding 1). Sink logits are
``sink_mean + sink_std x N(0, 1)`` and the selection bias ``e_bias_std x
N(0, 1)``, both float32, at the sizes the configuration file states under
``seeded_values``: chosen so that, on random inputs, the sink holds a good
part of a window layer's softmax mass and the bias changes a good part of
the selections (the file's ``assumed`` says how much; a zero sink or bias
would leave both untested).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest

from .shape import DENSE, FULL, MOE, WINDOW, dims  # noqa: F401 (dims is this file's by the family's contract)

def layer_leaves(d: dict, kind: tuple) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves that are plain
    draws; fan_in None = a norm scale (ones). The experts' matrices, the
    sink and the selection bias are made in ``make_layer``."""
    attn, ff = kind
    D, H, dqk, dv, Hk = d["D"], d["H"], d["dqk"], d["dv"], d["Hk"][attn]
    out = {
        ("attn", "q_proj"): ((D, H, dqk), D),
        ("attn", "k_proj"): ((D, Hk, dqk), D),
        ("attn", "v_proj"): ((D, Hk, dv), D),
        ("attn", "o_proj"): ((H * dv, D), H * dv),
        ("attn_norm", "scale"): ((D,), None),
        ("mlp_norm", "scale"): ((D,), None),
    }
    if ff == DENSE:
        out.update({("mlp", "gate_proj"): ((D, d["F"]), D), ("mlp", "up_proj"): ((D, d["F"]), D),
                    ("mlp", "down_proj"): ((d["F"], D), d["F"])})
    else:
        out[("moe", "router")] = ((D, d["E"]), D)
    return out


def make_layer(d: dict, key, layer, kind: tuple, dtype=jnp.float32) -> dict:
    """Layer ``layer``'s leaves (nested dict) for a traced or concrete
    index; matrices in ``dtype``, norm scales, sinks and the bias float32."""
    at = lambda path: jax.random.fold_in(leaf_key(key, ("layers",) + path), layer)
    flat = {path: draw(at(path), shape, fan_in, jnp.float32 if fan_in is None else dtype)
            for path, (shape, fan_in) in layer_leaves(d, kind).items()}
    if d["sink"][kind[0]]:
        flat[("attn", "sink")] = d["seeded"]["sink_mean"] + d["seeded"]["sink_std"] * jax.random.normal(
            at(("attn", "sink")), (d["H"],), jnp.float32)
    if kind[1] == MOE:
        first, n = d["held"]
        ids = first + jnp.arange(n, dtype=jnp.int32)
        D, Fe = d["D"], d["Fe"]
        for name, shape, fan_in in (("w_gate", (D, Fe), D), ("w_up", (D, Fe), D), ("w_down", (Fe, D), Fe)):
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
