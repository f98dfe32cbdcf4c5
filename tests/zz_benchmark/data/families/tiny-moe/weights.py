"""Seeded weights of the made-up ``tiny-moe`` family, as the program's
trainer lays a block with a layer of experts out: ``embed/embedding``,
``layers/{attn/{q,k,v,o}_proj/kernel, attn_norm/scale, mlp_norm/scale,
moe_mlp/{gate [D, E], w_in [E, D, F], w_out [E, F, D]}}``, ``final_norm/scale``,
``lm_head/kernel``. Matrices are normal with variance 1/fan_in."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.families._common import draw, leaf_key, nest


def dims(model: dict) -> dict:
    """``intermediate_size`` is one expert's width."""
    heads = int(model["num_attention_heads"])
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]), "L": int(model["num_hidden_layers"]),
        "H": heads, "K": int(model["num_key_value_heads"]),
        "hd": int(model.get("head_dim") or model["hidden_size"] // heads), "F": int(model["intermediate_size"]),
        "theta": float(model["rope_theta"]), "eps": float(model["rms_norm_eps"]),
        "E": int(model["num_local_experts"]), "k": int(model["num_experts_per_tok"]),
    }


def layer_leaves(d: dict) -> dict:
    """``path -> (shape, fan_in)``; fan_in None = a norm scale (ones)."""
    D, H, K, hd, E, F = d["D"], d["H"], d["K"], d["hd"], d["E"], d["F"]
    return {
        ("attn", "q_proj", "kernel"): ((D, H, hd), D),
        ("attn", "k_proj", "kernel"): ((D, K, hd), D),
        ("attn", "v_proj", "kernel"): ((D, K, hd), D),
        ("attn", "o_proj", "kernel"): ((H * hd, D), H * hd),
        ("attn_norm", "scale"): ((D,), None),
        ("mlp_norm", "scale"): ((D,), None),
        ("moe_mlp", "gate"): ((D, E), D),
        ("moe_mlp", "w_in"): ((E, D, F), D),
        ("moe_mlp", "w_out"): ((E, F, D), F),
    }


def outer_leaves(d: dict) -> dict:
    return {
        ("embed", "embedding"): ((d["V"], d["D"]), 1),
        ("final_norm", "scale"): ((d["D"],), None),
        ("lm_head", "kernel"): ((d["D"], d["V"]), d["D"]),
    }


def make_layer(d: dict, key, layer, dtype=jnp.float32) -> dict:
    return nest({
        path: draw(jax.random.fold_in(leaf_key(key, ("layers",) + path), layer), shape, fan_in, dtype)
        for path, (shape, fan_in) in layer_leaves(d).items()
    })


def make_outer(d: dict, key, dtype=jnp.float32) -> dict:
    return nest({path: draw(leaf_key(key, path), shape, fan_in, dtype)
                 for path, (shape, fan_in) in outer_leaves(d).items()})


def make_params(d: dict, key, dtype=jnp.float32) -> dict:
    tree = make_outer(d, key, dtype)
    tree["layers"] = jax.vmap(lambda l: make_layer(d, key, l, dtype))(jnp.arange(d["L"], dtype=jnp.int32))
    return tree
