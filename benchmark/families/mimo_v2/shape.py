"""The sizes of the ``mimo_v2`` family's layers from a configuration file's
keys, in plain Python: ``weights.py`` makes leaves from them on the device,
and ``flops.py`` counts from them inside the harness, which imports no JAX.
"""

from __future__ import annotations

FULL, WINDOW, DENSE, MOE = "full", "window", "dense", "moe"


def dims(model: dict) -> dict:
    """The sizes a layer needs, from a configuration file's published keys
    (and the three that state the chip's share: ``num_hidden_layers``,
    ``router_width``, ``experts_held``)."""
    L = int(model["num_hidden_layers"])
    dqk = int(model["head_dim"])
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]), "L": L,
        "H": int(model["num_attention_heads"]), "dqk": dqk, "dv": int(model["v_head_dim"]),
        "Hk": {FULL: int(model["num_key_value_heads"]), WINDOW: int(model["swa_num_key_value_heads"])},
        "rot": int(float(model["partial_rotary_factor"]) * dqk) // 2 * 2,
        "theta": {FULL: float(model["rope_theta"]), WINDOW: float(model["swa_rope_theta"])},
        "sink": {FULL: bool(model["add_full_attention_sink_bias"]),
                 WINDOW: bool(model["add_swa_attention_sink_bias"])},
        "window": int(model["sliding_window"]), "vscale": float(model["attention_value_scale"]),
        "F": int(model["intermediate_size"]), "Fe": int(model["moe_intermediate_size"]),
        "E": int(model["router_width"]), "held": tuple(int(x) for x in model["experts_held"]),
        "k": int(model["num_experts_per_tok"]), "eps": float(model["layernorm_epsilon"]),
        "seeded": {k: float(model["seeded_values"][k]) for k in ("sink_mean", "sink_std", "e_bias_std")},
        "kinds": tuple((WINDOW if w else FULL, MOE if m else DENSE) for w, m in
                       zip(model["hybrid_layer_pattern"][:L], model["moe_layer_freq"][:L], strict=True)),
    }
