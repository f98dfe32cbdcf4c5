"""Operations and bytes the ``nemotron_h`` family's serving path requires,
from a configuration file's published keys: what a token's forward computes,
the least a decode step over the batch must move through device memory, and
the state-space layers' part of that."""

from __future__ import annotations

from . import shape as W

BYTES = 2  # bfloat16 weights and keys and values, as the configuration states
STATE_BYTES = 4  # the scan's state is float32


def layer_params(d: dict, kind: str, experts: float) -> dict:
    """Matrix-product parameters of one layer by part (the convolution's
    taps and the per-head vectors with the Mamba layer's); ``experts`` = how
    many routed experts' matrices count (those held, or those a step
    touched)."""
    D = d["D"]
    if kind == W.MAMBA:
        return {"ssm": D * (d["di"] + d["C"] + d["mH"]) + d["di"] * D + (d["K"] + 1) * d["C"] + d["di"] + 3 * d["mH"]}
    if kind == W.ATTENTION:
        return {"attn": 2 * D * d["H"] * d["dh"] + 2 * D * d["Hk"] * d["dh"]}
    return {"router": D * d["E"], "shared": 2 * D * d["Fs"], "experts": experts * 2 * D * d["Fe"]}


def state_bytes_per_row(d: dict) -> float:
    """One row's constant state in one Mamba layer: the scan's float32 state
    and the convolution's tail."""
    return STATE_BYTES * d["mH"] * d["mP"] * d["N"] + BYTES * (d["K"] - 1) * d["C"]


def forward_flops_per_token(model: dict, position: float) -> float:
    """Operations one token's forward requires at cache position
    ``position``: 2 per matrix-product parameter (of an expert layer: the
    router, the shared expert and the ``num_experts_per_tok`` experts a
    token is routed to, of which this chip computes those it holds: ``k x
    held / E`` on average); the scan's update and read-out of the state (6
    operations an element); attention's two products over the positions the
    token sees."""
    d = W.dims(model)
    routed_here = d["k"] * d["held"][1] / d["E"]
    total = 2.0 * d["D"] * d["V"]
    for kind in d["kinds"]:
        total += 2.0 * sum(layer_params(d, kind, routed_here).values())
        if kind == W.MAMBA:
            total += 6.0 * d["mH"] * d["mP"] * d["N"]
        elif kind == W.ATTENTION:
            total += 2.0 * d["H"] * 2 * d["dh"] * (position + 1)
    return total


def ssm_step_bytes_min(model: dict, slots: float) -> float:
    """The least bytes the state-space layers must move in one decode step
    over ``slots`` occupied rows: each Mamba layer's weights read once, and
    each occupied row's state read AND written (the recurrence replaces it)."""
    d = W.dims(model)
    n = sum(1 for kind in d["kinds"] if kind == W.MAMBA)
    return n * (BYTES * layer_params(d, W.MAMBA, 0.0)["ssm"] + 2.0 * slots * state_bytes_per_row(d))


def decode_step_bytes_min(model: dict, slots: float, mean_positions: float, experts_touched: float) -> float:
    """The least bytes one decode step over ``slots`` occupied rows must
    move: every weight of the Mamba and attention layers, the routers, the
    shared experts and the head once (the embedding is ``slots`` rows, left
    out); of the routed experts only ``experts_touched`` (held experts that
    got a token, summed over the expert layers); each row's state-space
    state read and written; of the attention layers' slabs only the live
    positions (``mean_positions`` a row), keys and values."""
    d = W.dims(model)
    n_moe = sum(1 for kind in d["kinds"] if kind == W.EXPERTS)
    params = d["D"] * d["V"]
    cache = 0.0
    for kind in d["kinds"]:
        if kind == W.MAMBA:
            continue  # counted whole below
        params += sum(layer_params(d, kind, experts_touched / n_moe if n_moe else 0.0).values())
        if kind == W.ATTENTION:
            cache += slots * d["Hk"] * mean_positions * 2 * d["dh"]
    return BYTES * (params + cache) + ssm_step_bytes_min(model, slots)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """What ``family.py`` asks of every family's ``flops.py``: operations the
    forward and backward passes would require per token of a sequence of
    ``seq_len`` (three times the forward at the mean position). No cell
    trains this family."""
    return 3.0 * forward_flops_per_token(model, (seq_len - 1) / 2.0)
