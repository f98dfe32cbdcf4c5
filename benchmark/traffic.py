"""The one traffic generator. A mix is a data file under
``benchmark/traffic/``; this file turns it and a seed into a schedule.

Every seed gets the same work: the file's table of (prompt, answer)
lengths and, in an open loop, the gaps between arrivals — the exponential
distribution's own quantiles at the file's rate, in an order fixed by the
file — as one cycle, which the seed enters at another point. So runs differ
by where the cycle starts and by token values, never by how much there is
to do or by which long prompt meets which burst: on a server that admits
in lumps, that pairing alone moved the mean time to first token by a third
(PERF.md, PR 23). A file whose ``cycle_entry`` is a number has every seed
enter the cycle at that row, and the seed chooses token values alone: for a
mix whose repeats of one seed lie together and whose seeds lie apart, which
requests meet the empty engine at the window's start being what differs
(PERF.md, PR 26).
"""

from __future__ import annotations

import json
import math
import random
from pathlib import Path

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str, traffic_dir: Path = TRAFFIC_DIR) -> dict:
    path = Path(traffic_dir) / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"traffic mix {name!r}: no file {path}")
    return json.loads(path.read_text())


def rotate(items: list, seed: int, mix: dict) -> list:
    entry = mix.get("cycle_entry", "seed")
    k = (seed if entry == "seed" else int(entry)) % len(items)
    return items[k:] + items[:k]


def lengths_in_order(mix: dict, seed: int, count: int) -> list:
    """``count`` (prompt, answer) pairs: the table's first ``count`` entries
    (cycled where the table is shorter), entered at the seed's point."""
    table = [tuple(p) for p in mix["lengths"]]
    return rotate([table[i % len(table)] for i in range(count)], seed, mix)


def arrival_times(mix: dict, seed: int, seconds: float) -> list:
    """Open loop: ``round(rate * seconds)`` arrivals inside the window. The
    gaps are the quantiles of Exp(rate) at (i + 1/2) / n in the file's own
    fixed order, entered at the seed's point and scaled to end with the
    window: a Poisson stream's spread of gaps, and every seed the same gaps
    beside the same lengths."""
    n = max(1, round(float(mix["rate_per_s"]) * seconds))
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = seconds / (sum(gaps) + 1.0)  # one mean gap's room: the last arrival lies before the end
    random.Random(0).shuffle(gaps)  # the one fixed order of every run
    times, t = [], 0.0
    for g in rotate(gaps, seed, mix):
        t += g * scale
        times.append(t)
    return times


def prompt_tokens(seed: int, index: int, length: int, vocab: int) -> list:
    rng = random.Random((seed << 20) ^ index)
    return [rng.randrange(vocab) for _ in range(length)]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """The requests of one run. Open loop: one per arrival, with its due
    time. Closed loop: an ordered supply the clients draw from (due None),
    all of it made before the job starts. The rule for its length: the file's
    ``supply`` is at least twice its ``sent_a_window`` (what one window of the
    cell sends today, a chip run recorded in the file) and a multiple of the
    table's length, so that a longer supply begins with the shorter one's
    requests. A window that asks for more fails the run (``run.SupplyRanOut``):
    no cell fails before its program is twice as fast. The per-layer metric
    ``generator_supply_used_pct.serve_tps`` shows how near a run came."""
    if mix["loop"] == "open":
        due = arrival_times(mix, seed, seconds)
    elif mix["loop"] == "closed":
        due = [None] * int(mix["supply"])
    else:
        raise ValueError(f"traffic mix {mix.get('name')}: loop {mix['loop']!r} not open/closed")
    pairs = lengths_in_order(mix, seed, len(due))
    return [
        {"id": f"r{i:05d}", "due": t, "prompt_len": p, "max_new_tokens": a,
         "prompt": prompt_tokens(seed, i, p, vocab)}
        for i, (t, (p, a)) in enumerate(zip(due, pairs))
    ]
