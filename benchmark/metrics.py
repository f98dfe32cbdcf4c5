"""Metric arithmetic of the benchmark: statistics with their sample counts,
rates, the peaks table and the operations a training token requires."""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def mean(xs):
    xs = list(xs)
    return sum(xs) / len(xs) if xs else None


def percentile(xs, q):
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    xs = sorted(xs)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tokens_per_s(tokens, seconds):
    return tokens / seconds if seconds > 0 else None


def _share_inside(a: float, b: float, t0: float, t1: float) -> float:
    """The part of [a, b] that lies inside [t0, t1] (a point counts whole)."""
    if b <= a:
        return 1.0 if t0 <= a <= t1 else 0.0
    return max(0.0, min(b, t1) - max(a, t0)) / (b - a)


def tokens_processed(answers, t0: float, t1: float) -> float:
    """Prompt and generated tokens the engine worked through inside
    [t0, t1], from its own record of each request: admitted at ``submit_time
    + admit_wait_ms``, first token at ``submit_time + ttft_ms``, the last
    ``tpot_ms * (n - 1)`` later. The engine prefills one request at a time in
    equal chunks, so a prompt's tokens are credited evenly from admission to
    first token; the first token at its instant; the rest evenly to the end.
    A request that straddles an edge of the window counts for its part
    inside, so the sum moves smoothly with the work done, not in requests."""
    total = 0.0
    for a in answers:
        admit = a["submit_time"] + a["admit_wait_ms"] / 1e3
        first = a["submit_time"] + a["ttft_ms"] / 1e3
        n = len(a["tokens"])
        last = first + (a["tpot_ms"] or 0.0) / 1e3 * (n - 1)
        total += a["prompt_len"] * _share_inside(admit, first, t0, t1)
        total += _share_inside(first, first, t0, t1) + (n - 1) * _share_inside(first, last, t0, t1)
    return total


def peaks(device_kind: str, peaks_file: Path = PEAKS_FILE) -> dict:
    """Published peaks of one chip; a device not in the table is an error."""
    table = json.loads(Path(peaks_file).read_text())["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in {peaks_file} "
            f"(known: {sorted(table)}); add a sourced row, do not default"
        )
    return table[device_kind]


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product: the projections, the
    feed-forward and the output head. The input embedding is a lookup and
    the norm scales are element-wise; neither counts."""
    D, L = model["hidden_size"], model["num_hidden_layers"]
    H, K = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or D // H
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * model["intermediate_size"]
    return L * per_layer + D * model["vocab_size"]


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Operations the forward and backward passes require per token:
    6 per matrix-product parameter, plus causal attention's two products
    (scores and values) at 6 * L * S * (H * hd): 4 * S * H * hd forward over
    the full square, halved by the causal mask, times 3 for the backward.
    Recomputation is not counted."""
    H = model["num_attention_heads"]
    hd = model.get("head_dim") or model["hidden_size"] // H
    return 6.0 * matmul_params(model) + 6.0 * model["num_hidden_layers"] * seq_len * H * hd


def mfu_pct(model: dict, seq_len: int, tokens_per_s_chip: float, device_kind: str) -> float:
    return 100.0 * train_flops_per_token(model, seq_len) * tokens_per_s_chip / peaks(device_kind)["bf16_flops"]
