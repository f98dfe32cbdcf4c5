"""The sizes of the ``jamba`` family's layers from a configuration file's
keys, in plain Python: ``weights.py`` makes leaves from them on the device,
and ``flops.py`` counts from them inside the harness, which imports no JAX.
"""

from __future__ import annotations

import math

MAMBA, FULL = "mamba", "attn_full"


def layer_kinds(n: int, offset: int, period: int) -> tuple:
    """The mixer of each of ``n`` layers: layer ``i`` carries attention iff
    ``(i - offset) % period == 0`` (the ``jamba`` configuration class's
    convention for ``attn_layer_offset`` / ``attn_layer_period``), else a
    Mamba-1 mixer. Every layer carries the dense feed-forward beside it."""
    return tuple(FULL if (i - offset) % period == 0 else MAMBA for i in range(n))


def dims(model: dict) -> dict:
    """The sizes a layer needs: the published keys, and the one size the
    published configuration leaves to its class's default (``head_dim``),
    which the file states under ``assumed_sizes`` with its reason under
    ``assumed``."""
    if model["num_experts"] != 1 or not model["tie_word_embeddings"] or model["mamba_proj_bias"] or not model["mamba_conv_bias"]:
        raise ValueError("the family is written for one expert (a dense feed-forward in every layer), a tied head, "
                         "a bias on the convolution and none on the Mamba projections")
    if model.get("sliding_window") is not None:
        raise ValueError("the family's attention has no window")
    L, D, H, Hk = (int(model[k]) for k in ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads"))
    if D % H or H % Hk or model["assumed_sizes"]["head_dim"] != D // H:
        raise ValueError("heads divide the width, key/value heads the heads, and assumed_sizes.head_dim is hidden / heads")
    if int(model["mamba_dt_rank"]) != math.ceil(D / 16):
        raise ValueError("mamba_dt_rank is ceil(hidden / 16)")
    kinds = layer_kinds(L, int(model["attn_layer_offset"]), int(model["attn_layer_period"]))
    return {
        "V": int(model["vocab_size"]), "D": D, "L": L, "kinds": kinds, "H": H, "Hk": Hk, "dh": D // H,
        "F": int(model["intermediate_size"]), "di": int(model["mamba_expand"]) * D, "N": int(model["mamba_d_state"]),
        "K": int(model["mamba_d_conv"]), "R": int(model["mamba_dt_rank"]), "eps": float(model["rms_norm_eps"]),
        "seeded": {k: float(v) for k, v in model["seeded_values"].items()},
    }
