"""Operations and bytes the ``phi4_flash`` family's serving path requires,
from a configuration file's keys: what a token's forward computes, the least
a decode step over the batch must move through device memory, and the parts
of that which the state-space layers and the ONE shared slab take."""

from __future__ import annotations

from . import shape as W

BYTES = 2  # bfloat16 weights and keys and values, as the configuration states
STATE_BYTES = 4  # the scan's state is float32


def layer_params(d: dict, kind: str) -> dict:
    """Parameters of one layer by part (norms, biases, the convolution and
    the per-channel vectors with their mixer)."""
    D, F, di, dh = d["D"], d["F"], d["di"], d["dh"]
    out = {"mlp": 3 * D * F, "norms": 4 * D}
    if kind in (W.MAMBA, W.MAMBA_MEMORY):
        out["ssm"] = (D * 2 * di + di * (d["R"] + 2 * d["N"]) + d["R"] * di + di * D
                      + (d["K"] + 1) * di + d["N"] * di + 2 * di)
    elif kind == W.GMU:
        out["gmu"] = 2 * D * di
    else:
        out["attn"] = 2 * D * D + 2 * D + 6 * dh + (0 if kind == W.CROSS else 2 * D * d["Hk"] * dh + 2 * d["Hk"] * dh)
    return out


def parameters(model: dict) -> int:
    """Every parameter of the model; the embedding, which is also the head, once."""
    d = W.dims(model)
    return d["V"] * d["D"] + 2 * d["D"] + sum(sum(layer_params(d, kind).values()) for kind in d["kinds"])


def kv_bytes_per_position(d: dict) -> int:
    """One position's keys and values in one layer's cache."""
    return BYTES * 2 * d["Hk"] * d["dh"]


def state_bytes_per_row(d: dict) -> float:
    """One row's constant state in one Mamba layer: the scan's float32 state
    and the convolution's tail."""
    return STATE_BYTES * d["N"] * d["di"] + BYTES * (d["K"] - 1) * d["di"]


def forward_flops_per_token(model: dict, position: float) -> float:
    """Operations one token's forward through EVERY layer requires at cache
    position ``position``: 2 per matrix-product parameter, the head's
    product; the scan's update and read-out of the state (6 operations an
    element); attention's two products, twice over for the two softmaxes of
    a pair (each over ``head_dim`` keys and ``2 head_dim`` values), over the
    positions the layer sees."""
    d = W.dims(model)
    total = 2.0 * d["D"] * d["V"]
    for kind in d["kinds"]:
        total += 2.0 * sum(layer_params(d, kind).values())
        if kind in (W.MAMBA, W.MAMBA_MEMORY):
            total += 6.0 * d["N"] * d["di"]
        elif kind != W.GMU:
            seen = min(position + 1, d["window"]) if kind == W.WINDOW else position + 1
            total += 2.0 * d["H"] * (d["dh"] + 2 * d["dh"]) * seen
    return total


def mamba_layers(d: dict) -> int:
    return sum(kind in (W.MAMBA, W.MAMBA_MEMORY) for kind in d["kinds"])


def ssm_step_bytes_min(model: dict, slots: float) -> float:
    """The least bytes the state-space layers must move in one decode step
    over ``slots`` occupied rows: each Mamba mixer's weights read once, and
    each occupied row's state read AND written (the recurrence replaces it)."""
    d = W.dims(model)
    return mamba_layers(d) * (BYTES * layer_params(d, W.MAMBA)["ssm"] + 2.0 * slots * state_bytes_per_row(d))


def shared_kv_step_bytes_min(model: dict, slots: float, mean_positions: float) -> float:
    """The least bytes one decode step must move of the ONE slab: each
    occupied row's live positions (``mean_positions`` a row), keys and values
    once, for each of the layers that attend it (its owner and the cross
    layers: no kernel shares the walk between layers)."""
    d = W.dims(model)
    return float(W.readers(d)) * slots * mean_positions * kv_bytes_per_position(d)


def decode_step_bytes_min(model: dict, slots: float, mean_positions: float) -> float:
    """The least bytes one decode step over ``slots`` occupied rows must
    move: every weight once (the embedding once, as the head; the ``slots``
    rows gathered from it are left out); the one slab's live positions once
    a reader; each ring's live positions (at most the window); each row's
    state-space state read and written."""
    d = W.dims(model)
    rings = sum(kind == W.WINDOW for kind in d["kinds"])
    return (BYTES * parameters(model) + mamba_layers(d) * 2.0 * slots * state_bytes_per_row(d)
            + shared_kv_step_bytes_min(model, slots, mean_positions)
            + rings * slots * min(mean_positions, d["window"]) * kv_bytes_per_position(d))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """What ``family.py`` asks of every family's ``flops.py``: operations the
    forward and backward passes would require per token of a sequence of
    ``seq_len`` (three times the forward at the mean position). No cell
    trains this family."""
    return 3.0 * forward_flops_per_token(model, (seq_len - 1) / 2.0)
