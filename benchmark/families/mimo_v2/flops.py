"""Operations and bytes the ``mimo_v2`` family's serving path requires, from
a configuration file's published keys: what a token's forward computes, and
the least a decode step over the batch must read from device memory."""

from __future__ import annotations

from . import shape as W

BYTES = 2  # bfloat16 weights and cache, as the configuration states


def layer_params(d: dict, kind: tuple, experts: float) -> dict:
    """Matrix-product parameters of one layer by part; ``experts`` = how
    many experts' matrices count (those held, or those a step touched)."""
    attn, ff = kind
    D, H, Hk = d["D"], d["H"], d["Hk"][attn]
    out = {"attn": D * H * d["dqk"] + D * Hk * (d["dqk"] + d["dv"]) + H * d["dv"] * D}
    if ff == W.DENSE:
        out["dense_mlp"] = 3 * D * d["F"]
    else:
        out["router"] = D * d["E"]
        out["experts"] = experts * 3 * D * d["Fe"]
    return out


def forward_flops_per_token(model: dict, position: float) -> float:
    """Operations one token's forward requires at cache position
    ``position``: 2 per matrix-product parameter (of an expert layer: the
    router and the ``num_experts_per_tok`` experts a token is routed to, of
    which this chip computes those it holds: ``k x held / E`` on average),
    plus attention's two products over the positions the token sees."""
    d = W.dims(model)
    routed_here = d["k"] * d["held"][1] / d["E"]
    total = 2.0 * d["D"] * d["V"]
    for kind in d["kinds"]:
        total += 2.0 * sum(layer_params(d, kind, routed_here).values())
        seen = position + 1 if kind[0] == W.FULL else min(position + 1, d["window"])
        total += 2.0 * d["H"] * (d["dqk"] + d["dv"]) * seen
    return total


def decode_step_bytes_min(model: dict, slots: float, mean_positions: float, experts_touched: float) -> float:
    """The least bytes one decode step over ``slots`` occupied rows must
    read: every weight of attention, the dense layer, the routers and the
    head once (the embedding is ``slots`` rows, left out); of the experts
    only ``experts_touched`` (held experts that got a token, summed over the
    expert layers); of each cache kind only the live positions
    (``mean_positions`` a row in a full layer, at most the window in a
    window layer), keys and values."""
    d = W.dims(model)
    n_moe = sum(1 for kind in d["kinds"] if kind[1] == W.MOE)
    params = d["D"] * d["V"]
    cache = 0.0
    for kind in d["kinds"]:
        parts = layer_params(d, kind, experts_touched / n_moe if n_moe else 0.0)
        params += sum(parts.values())
        live = mean_positions if kind[0] == W.FULL else min(mean_positions, d["window"])
        cache += slots * d["Hk"][kind[0]] * live * (d["dqk"] + d["dv"])
    return BYTES * (params + cache)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """What ``family.py`` asks of every family's ``flops.py``: operations the
    forward and backward passes would require per token of a sequence of
    ``seq_len`` (three times the forward at the mean position; a window
    layer's saturation is taken at that mean). No cell trains this family."""
    return 3.0 * forward_flops_per_token(model, (seq_len - 1) / 2.0)
