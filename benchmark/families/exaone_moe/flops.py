"""Operations and bytes the ``exaone_moe`` family's serving path requires,
from a configuration file's published keys: what a token's forward computes,
and the least a verifying decode step over the batch must read from device
memory, all of it and the part its two walks of a slab read."""

from __future__ import annotations

from . import shape as W

BYTES = 2  # bfloat16 weights and cache, as the configuration states


def forward_flops_per_token(model: dict, position: float) -> float:
    """Operations one token's forward through the main stack requires at
    cache position ``position``: 2 per matrix-product parameter (of a sparse
    layer: the router, the shared expert and the ``num_experts_per_tok``
    experts a token is routed to, of which this chip computes those it
    holds: ``k x held / E`` on average), plus attention's two products over
    the positions the token sees, and the head's slice."""
    d = W.dims(model)
    routed_here = d["k"] * d["held"][1] / d["E"]
    total = 2.0 * d["D"] * d["V"]
    for kind in d["kinds"]:
        total += 2.0 * sum(W.layer_params(d, kind, routed_here).values())
        seen = position + 1 if kind[0] == W.FULL else min(position + 1, d["window"])
        total += 2.0 * d["H"] * 2 * d["dh"] * seen
    return total


def spec_step_bytes_min(model: dict, slots: float, mean_positions: float, experts_touched: float) -> float:
    """The least bytes one VERIFYING decode step over ``slots`` occupied rows
    must read, whatever it yields: every weight of the main stack's layers,
    of the multi-token-prediction block (its joining product too) and of the
    head's slice once (the embedding is a few rows, left out); of the routed
    experts only ``experts_touched`` (held experts that got a token, summed
    over the sparse layers and the block); of the cache each row's live
    positions (``mean_positions`` a row in the full layer's slab and in the
    block's, at most the window in each ring), keys and values."""
    d = W.dims(model)
    sparse = [kind for kind in d["kinds"] if kind[1] == W.MOE] + [W.MTP_KIND]
    touched = experts_touched / len(sparse)
    params = d["D"] * d["V"] + 2 * d["D"] * d["D"]
    params += sum(sum(W.layer_params(d, kind, touched).values()) for kind in d["kinds"] + (W.MTP_KIND,))
    full = sum(1 for kind in d["kinds"] if kind[0] == W.FULL) + 1  # the block's slab
    rings = len(d["kinds"]) - (full - 1)
    live = full * mean_positions + rings * min(mean_positions, d["window"])
    return BYTES * (params + slots * d["Hk"] * 2 * d["dh"] * live)


def walk_step_bytes_min(model: dict, slots: float, mean_positions: float) -> float:
    """The least bytes the walks of a verifying step must read: each occupied
    row's live positions of the full layer's slab and of the block's, keys
    and values once (a row's two queries share a walk)."""
    d = W.dims(model)
    slabs = sum(1 for kind in d["kinds"] if kind[0] == W.FULL) + 1
    return BYTES * slots * slabs * d["Hk"] * 2 * d["dh"] * mean_positions


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """What ``family.py`` asks of every family's ``flops.py``: operations the
    forward and backward passes would require per token of a sequence of
    ``seq_len`` (three times the forward at the mean position). No cell
    trains this family."""
    return 3.0 * forward_flops_per_token(model, (seq_len - 1) / 2.0)
