"""The sizes of the ``exaone_moe`` family's layers from a configuration
file's keys, in plain Python: ``weights.py`` makes leaves from them on the
device, and ``flops.py`` counts from them inside the harness, which imports
no JAX.
"""

from __future__ import annotations

import math

FULL, WINDOW, DENSE, MOE = "full", "window", "dense", "moe"
ATTENTION = {"full_attention": FULL, "sliding_attention": WINDOW}
FEED_FORWARD = {"dense": DENSE, "sparse": MOE}
MTP_KIND = (FULL, MOE)


def dims(model: dict) -> dict:
    """The sizes a layer needs, from a configuration file's published keys
    and the three that state the chip's share (``num_hidden_layers``,
    ``num_experts`` held of ``router_width`` at ``experts_held``,
    ``vocab_size`` of ``published_vocab_size``)."""
    if (model["n_group"], model["topk_group"]) != (1, 1) or not model["norm_topk_prob"] \
            or model["scoring_func"] != "sigmoid" or model["tie_word_embeddings"]:
        raise ValueError("the family is written for one router group, renormalised sigmoid scores and an untied head")
    if model["num_nextn_predict_layers"] != 1 or model["mtp_layer_types"] != ["full_attention"]:
        raise ValueError("the family is written for ONE multi-token-prediction block of the full-attention kind")
    L, held = int(model["num_hidden_layers"]), tuple(int(x) for x in model["experts_held"])
    if held[1] != int(model["num_experts"]):
        raise ValueError("num_experts counts the experts held here: experts_held's count")
    kinds = tuple((ATTENTION[a], FEED_FORWARD[f]) for a, f in
                  zip(model["layer_types"][:L], model["mlp_layer_types"][:L], strict=True))
    if any((f == DENSE) != (l < int(model["first_k_dense_replace"])) for l, (_, f) in enumerate(kinds)):
        raise ValueError("mlp_layer_types and first_k_dense_replace disagree")
    seeded = {k: float(v) for k, v in model["seeded_values"].items()}
    return {
        "V": int(model["vocab_size"]), "D": int(model["hidden_size"]), "L": L, "kinds": kinds,
        "H": int(model["num_attention_heads"]), "Hk": int(model["num_key_value_heads"]), "dh": int(model["head_dim"]),
        "theta": float(model["rope_parameters"]["rope_theta"]), "window": int(model["sliding_window"]),
        "F": int(model["intermediate_size"]), "Fe": int(model["moe_intermediate_size"]),
        "Fs": int(model["moe_intermediate_size"]) * int(model["num_shared_experts"]),
        "E": int(model["router_width"]), "held": held, "k": int(model["num_experts_per_tok"]),
        "scale": float(model["routed_scaling_factor"]), "eps": float(model["rms_norm_eps"]),
        "seeded": seeded,
        # Every matrix that writes to the residual stream is drawn x this (configuration file, ``assumed``).
        "residual": 1.0 / math.sqrt(2.0 * seeded["residual_layers"]),
    }


def layer_params(d: dict, kind: tuple, experts: float) -> dict:
    """Matrix-product parameters of one layer by part; ``experts`` = how
    many routed experts' matrices count (those held, or those a step touched)."""
    D, H, Hk, dh = d["D"], d["H"], d["Hk"], d["dh"]
    out = {"attn": 2 * D * H * dh + 2 * D * Hk * dh}
    if kind[1] == DENSE:
        out["dense_mlp"] = 3 * D * d["F"]
    else:
        out.update(router=D * d["E"], shared=3 * D * d["Fs"], experts=experts * 3 * D * d["Fe"])
    return out


def parameters(d: dict) -> dict:
    """Parameters held here by part (norm scales and the selection bias left
    out: under 0.1 M): what the configuration file's ``bytes`` are counted from."""
    n = d["held"][1]
    layers = [sum(layer_params(d, kind, n).values()) for kind in d["kinds"]]
    return {"layers": layers, "mtp": 2 * d["D"] * d["D"] + sum(layer_params(d, MTP_KIND, n).values()),
            "vocabulary": 2 * d["V"] * d["D"]}


def cache_bytes(d: dict, slots: int, length: int, chunk: int, itemsize: int = 2) -> dict:
    """Bytes of the cache for ``slots`` rows: a slab of ``length`` positions
    a full layer and one for the block, a ring of ``window + chunk`` a window
    layer with an int32 position an entry."""
    row = 2 * d["Hk"] * d["dh"] * itemsize
    ring = min(length, d["window"] + chunk)
    full = sum(1 for a, _ in d["kinds"] if a == FULL)
    return {"full": slots * full * length * row, "mtp": slots * length * row,
            "window": slots * (len(d["kinds"]) - full) * ring * (row + 4)}
