"""The sizes of the ``nemotron_h`` family's layers from a configuration
file's keys, in plain Python: ``weights.py`` makes leaves from them on the
device, and ``flops.py`` counts from them inside the harness, which imports
no JAX.
"""

from __future__ import annotations

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def dims(model: dict) -> dict:
    """The sizes a layer needs, from a configuration file's published keys
    (and the three that state the chip's share: ``num_hidden_layers``,
    ``router_width``, ``experts_held``). The Mamba layer's inner width is
    ``mamba_num_heads x mamba_head_dim`` (``expand`` is not read), its
    convolution runs over that plus the B and C of ``n_groups`` groups of
    ``ssm_state_size``; ``n_group`` (1) is the router's and means no group
    step."""
    L = int(model["num_hidden_layers"])
    kinds = tuple(model["hybrid_override_pattern"][:L])
    if len(kinds) != L or set(kinds) - {MAMBA, ATTENTION, EXPERTS}:
        raise ValueError(f"hybrid_override_pattern gives no {L} layers of M, * and E: {kinds}")
    if model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("the family routes without a group step: n_group and topk_group must be 1")
    H, P, G, N = (int(model[k]) for k in ("mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size"))
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]), "L": L, "kinds": kinds,
        "H": int(model["num_attention_heads"]), "Hk": int(model["num_key_value_heads"]), "dh": int(model["head_dim"]),
        "mH": H, "mP": P, "G": G, "N": N, "K": int(model["conv_kernel"]), "di": H * P, "C": H * P + 2 * G * N,
        "Fe": int(model["moe_intermediate_size"]),
        "Fs": int(model["moe_shared_expert_intermediate_size"]) * int(model["n_shared_experts"]),
        "E": int(model["router_width"]), "held": tuple(int(x) for x in model["experts_held"]),
        "k": int(model["num_experts_per_tok"]), "scale": float(model["routed_scaling_factor"]),
        "eps": float(model["layer_norm_epsilon"]),
        "dt": tuple(float(model[k]) for k in ("time_step_min", "time_step_max", "time_step_floor")),
        "seeded": {k: float(model["seeded_values"][k]) for k in ("e_bias_std", "residual_out_scale")},
        # the check's band around a selection's edge (reference.py), stated by the configuration
        "edge": float(model["check"]["edge"]),
    }
