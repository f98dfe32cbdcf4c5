"""Operations and bytes the ``jamba`` family's serving path requires, from a
configuration file's keys: what a token's forward computes, the least a decode
step over the batch must move through device memory, and the parts of that
which the state-space layers and the walks of the attention layers' slabs take."""

from __future__ import annotations

from . import shape as W

BYTES = 2  # bfloat16 weights and keys and values, as the configuration states
STATE_BYTES = 4  # the scan's state is float32


def layer_params(d: dict, kind: str) -> dict:
    """Parameters of one layer by part (the convolution, the per-channel
    vectors and the inner norms with their mixer)."""
    D, F, di, dh = d["D"], d["F"], d["di"], d["dh"]
    out = {"mlp": 3 * D * F, "norms": 2 * D}
    if kind == W.MAMBA:
        out["ssm"] = ssm_matrix_params(d) + (d["K"] + 1) * di + d["N"] * di + 2 * di + d["R"] + 2 * d["N"]
    else:
        out["attn"] = 2 * D * d["H"] * dh + 2 * D * d["Hk"] * dh
    return out


def ssm_matrix_params(d: dict) -> int:
    """A Mamba mixer's four matrices: ``in_proj``, ``x_proj``, ``dt_proj``, ``out_proj``."""
    D, di = d["D"], d["di"]
    return D * 2 * di + di * (d["R"] + 2 * d["N"]) + d["R"] * di + di * D


def parameters(model: dict) -> int:
    """Every parameter of the model; the embedding, which is also the head, once."""
    d = W.dims(model)
    return d["V"] * d["D"] + d["D"] + sum(sum(layer_params(d, kind).values()) for kind in d["kinds"])


def kv_bytes_per_position(d: dict) -> int:
    """One position's keys and values in one attention layer's slab."""
    return BYTES * 2 * d["Hk"] * d["dh"]


def state_bytes_per_row(d: dict) -> float:
    """One row's constant state in one Mamba layer: the scan's float32 state
    and the convolution's tail."""
    return STATE_BYTES * d["N"] * d["di"] + BYTES * (d["K"] - 1) * d["di"]


def mamba_layers(d: dict) -> int:
    return sum(kind == W.MAMBA for kind in d["kinds"])


def attention_layers(d: dict) -> int:
    return sum(kind == W.FULL for kind in d["kinds"])


def forward_flops_per_token(model: dict, position: float, head: bool = True) -> float:
    """Operations one token's forward through every layer requires at cache
    position ``position``: 2 per matrix-product parameter; the scan's update
    and read-out of the state (6 operations an element); attention's two
    products over the ``position + 1`` positions the token sees; and, with
    ``head``, the head's product (the embedding once more). A prefill chunk
    runs no head: a prompt's tokens cost ``head=False``, and the head's
    product once a prompt."""
    d = W.dims(model)
    total = head_flops(model) if head else 0.0
    for kind in d["kinds"]:
        parts = layer_params(d, kind)
        total += 2.0 * (parts["mlp"] + parts.get("attn", 0))
        if kind == W.MAMBA:
            total += 2.0 * ssm_matrix_params(d) + 6.0 * d["N"] * d["di"]
        else:
            total += 2.0 * d["H"] * 2 * d["dh"] * (position + 1)
    return total


def head_flops(model: dict) -> float:
    """The head's product for one token."""
    d = W.dims(model)
    return 2.0 * d["D"] * d["V"]


def ssm_step_bytes_min(model: dict, slots: float) -> float:
    """The least bytes the state-space layers must move in one decode step
    over ``slots`` occupied rows: each Mamba mixer's weights read once, and
    each occupied row's state read AND written (the recurrence replaces it)."""
    d = W.dims(model)
    return mamba_layers(d) * (BYTES * layer_params(d, W.MAMBA)["ssm"] + 2.0 * slots * state_bytes_per_row(d))


def walk_step_bytes_min(model: dict, slots: float, mean_positions: float) -> float:
    """The least bytes one decode step's walks must read: each occupied row's
    live positions (``mean_positions`` a row) of each attention layer's slab,
    keys and values once (ONE key/value head serves the row's 20 queries)."""
    d = W.dims(model)
    return float(attention_layers(d)) * slots * mean_positions * kv_bytes_per_position(d)


def decode_step_bytes_min(model: dict, slots: float, mean_positions: float) -> float:
    """The least bytes one decode step over ``slots`` occupied rows must
    move: every weight once (the embedding once, as the head; the ``slots``
    rows gathered from it are left out); the slabs' live positions; each
    row's state-space state read and written."""
    d = W.dims(model)
    return (BYTES * parameters(model) + mamba_layers(d) * 2.0 * slots * state_bytes_per_row(d)
            + walk_step_bytes_min(model, slots, mean_positions))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """What ``family.py`` asks of every family's ``flops.py``: operations the
    forward and backward passes would require per token of a sequence of
    ``seq_len`` (three times the forward at the mean position). No cell
    trains this family."""
    return 3.0 * forward_flops_per_token(model, (seq_len - 1) / 2.0)
