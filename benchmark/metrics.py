"""Metric arithmetic of the benchmark: statistics with their sample counts,
rates, and the peaks table."""

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


def mfu_pct(flops_per_token: float, tokens_per_s_chip: float, device_kind: str) -> float:
    """``flops_per_token`` is the configuration's family's count
    (``families/<family>/flops.py``) of the operations a token requires."""
    return 100.0 * flops_per_token * tokens_per_s_chip / peaks(device_kind)["bf16_flops"]
