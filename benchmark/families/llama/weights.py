"""Seeded weights for the benchmark's cells, made by the benchmark.

The program under test and the plain reference both start from the values
this file makes, so neither takes anything the other produced. Every leaf
is drawn from a key folded from the run's key, the leaf's path and, for a
per-layer leaf, the layer index: a whole stacked tree (what the program
holds) and one layer at a time (what the reference can hold) give the same
numbers.

Layout follows a Llama-shaped block: ``embed/embedding [V, D]``,
``layers/{attn/{q,k,v,o}_proj, mlp/{gate,up,down}_proj}/kernel``,
``layers/{attn,mlp}_norm/scale``, ``final_norm/scale``, ``lm_head/kernel
[D, V]``. Matrices are normal with variance 1/fan_in (the embedding 1), so
the logits of the seeded model have unit variance and the first loss is
ln V + 1/2.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest


def dims(model: dict) -> dict:
    """The sizes a block needs, from a configuration file's published keys."""
    heads = int(model["num_attention_heads"])
    return {
        "V": int(model["vocab_size"]),
        "D": int(model["hidden_size"]),
        "L": int(model["num_hidden_layers"]),
        "H": heads,
        "K": int(model["num_key_value_heads"]),
        "hd": int(model.get("head_dim") or model["hidden_size"] // heads),
        "F": int(model["intermediate_size"]),
        "theta": float(model["rope_theta"]),
        "eps": float(model["rms_norm_eps"]),
    }


def layer_leaves(d: dict) -> dict:
    """``path -> (shape, fan_in)`` of one layer's leaves; fan_in None = a
    norm scale (ones)."""
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    return {
        ("attn", "q_proj", "kernel"): ((D, H, hd), D),
        ("attn", "k_proj", "kernel"): ((D, K, hd), D),
        ("attn", "v_proj", "kernel"): ((D, K, hd), D),
        ("attn", "o_proj", "kernel"): ((H * hd, D), H * hd),
        ("attn_norm", "scale"): ((D,), None),
        ("mlp", "gate_proj", "kernel"): ((D, F), D),
        ("mlp", "up_proj", "kernel"): ((D, F), D),
        ("mlp", "down_proj", "kernel"): ((F, D), F),
        ("mlp_norm", "scale"): ((D,), None),
    }


def outer_leaves(d: dict) -> dict:
    return {
        ("embed", "embedding"): ((d["V"], d["D"]), 1),
        ("final_norm", "scale"): ((d["D"],), None),
        ("lm_head", "kernel"): ((d["D"], d["V"]), d["D"]),
    }


def make_layer(d: dict, key, layer, dtype=jnp.float32) -> dict:
    """One layer's leaves (nested dict), for a traced or concrete index."""
    return nest({
        path: draw(jax.random.fold_in(leaf_key(key, ("layers",) + path), layer), shape, fan_in, dtype)
        for path, (shape, fan_in) in layer_leaves(d).items()
    })


def make_outer(d: dict, key, dtype=jnp.float32, only=None) -> dict:
    return nest({
        path: draw(leaf_key(key, path), shape, fan_in, dtype)
        for path, (shape, fan_in) in outer_leaves(d).items()
        if only is None or path[0] in only
    })


def make_params(d: dict, key, dtype=jnp.float32) -> dict:
    """The whole tree, per-layer leaves stacked on a leading axis of L."""
    tree = make_outer(d, key, dtype)
    tree["layers"] = jax.vmap(lambda l: make_layer(d, key, l, dtype))(
        jnp.arange(d["L"], dtype=jnp.int32)
    )
    return tree
