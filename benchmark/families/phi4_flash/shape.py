"""The sizes of the ``phi4_flash`` family's layers from a configuration
file's keys, in plain Python: ``weights.py`` makes leaves from them on the
device, and ``flops.py`` counts from them inside the harness, which imports
no JAX.
"""

from __future__ import annotations

import math

MAMBA, MAMBA_MEMORY, WINDOW, FULL, GMU, CROSS = (
    "mamba", "mamba_memory", "attn_window", "attn_full", "gmu", "attn_cross",
)


def layer_kinds(n: int) -> tuple:
    """(mixer, what it owns) of each of ``n`` layers at ``mb_per_layer`` 2:
    the self-decoder is layers ``0 .. n/2 + 1`` (even: Mamba-1, of which layer
    ``n/2`` also hands its scan output on; odd: window attention; layer ``n/2
    + 1``: THE full attention layer), the cross-decoder the rest (even: a
    gated memory unit; odd: cross-attention onto the full layer's keys and
    values)."""
    half = n // 2
    kinds = []
    for l in range(n):
        if l <= half:
            kinds.append((MAMBA_MEMORY if l == half else MAMBA) if l % 2 == 0 else WINDOW)
        else:
            kinds.append(FULL if l == half + 1 else (GMU if l % 2 == 0 else CROSS))
    return tuple(kinds)


def dims(model: dict) -> dict:
    """The sizes a layer needs: the published keys, and the sizes the
    published configuration leaves to its class's defaults, which the file
    states under ``assumed_sizes`` (each with its reason under ``assumed``)."""
    if model["mb_per_layer"] != 2 or not model["tie_word_embeddings"] or model["mlp_bias"] or model["lm_head_bias"]:
        raise ValueError("the family is written for mb_per_layer 2, a tied head and no mlp or head bias")
    L, D, H, Hk = (int(model[k]) for k in ("num_hidden_layers", "hidden_size", "num_attention_heads", "num_key_value_heads"))
    a = model["assumed_sizes"]
    if L < 4 or L % 2 or D % H or H % Hk or Hk % 2:
        raise ValueError("layers must be even, heads divide the width and key/value heads pair up")
    if a["head_dim"] != D // H or a["mamba_dt_rank"] != math.ceil(D / 16):
        raise ValueError("assumed_sizes: head_dim is hidden / heads and dt_rank is ceil(hidden / 16)")
    return {
        "V": int(model["vocab_size"]), "D": D, "L": L, "kinds": layer_kinds(L), "full": L // 2 + 1,
        "H": H, "Hk": Hk, "dh": D // H, "F": int(model["intermediate_size"]), "window": int(model["sliding_window"]),
        "di": int(a["mamba_expand"]) * D, "N": int(a["mamba_d_state"]), "K": int(a["mamba_d_conv"]),
        "R": int(a["mamba_dt_rank"]), "eps": float(model["layer_norm_eps"]),
        "seeded": {k: float(v) for k, v in model["seeded_values"].items()},
    }


def readers(d: dict) -> int:
    """Layers that attend the one slab: its owner and the cross layers."""
    return sum(kind in (FULL, CROSS) for kind in d["kinds"])
