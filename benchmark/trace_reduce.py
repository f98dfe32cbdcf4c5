"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` (no TensorFlow)
and works on intervals only: the device is *busy* where an event of a
device plane's ``XLA Ops`` line runs, the *window* is the span from the
first to the last such event, a *program*'s time is the part of the busy
set inside the intervals of its ``XLA Modules`` events, and everything is
clipped to the window — so a share is a part of a whole and cannot pass
100. Runs in the process that took the trace (the replica holds the chip).
"""

from __future__ import annotations

import glob
import os
import re

COLLECTIVE = re.compile(r"all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute")


def merge(intervals):
    """Union of (start, end) intervals as a sorted, disjoint list."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged if min(e, hi) > max(s, lo)]


def length(merged):
    return sum(e - s for s, e in merged)


def intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo, hi):
    edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
    return [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2) if edges[k + 1] > edges[k]]


def short_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``; names stay
    inside the characters a ledger line takes."""
    name = name.split(" = ")[0].lstrip("%$")
    return re.sub(r"[^A-Za-z0-9_.:-]", "_", name)[:60]


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_planes(path: str) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns)]}}`` of the planes the
    reduction reads: the device planes and the host's threads."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not (plane.name.startswith("/device:TPU") or plane.name == "/host:CPU"):
            continue
        lines = {}
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
            if evs:
                lines[line.name] = evs
        out[plane.name] = lines
    return out


def reduce_planes(planes: dict, programs=()) -> dict:
    """The summary one traced process reports. ``programs`` are substrings
    of ``XLA Modules`` names (``prefill_chunk``) whose share of the busy
    time is wanted. Times in seconds, averaged over the device planes."""
    devices = {n: ls for n, ls in planes.items() if n.startswith("/device:TPU") and ls.get("XLA Ops")}
    if not devices:
        return {}
    host = [ev for line in planes.get("/host:CPU", {}).values() for ev in line]
    busy_s = window_s = collective_s = 0.0
    program_s = {p: 0.0 for p in programs}
    program_runs = {p: [] for p in programs}
    op_time: dict = {}
    idle = []
    for lines in devices.values():
        ops = lines["XLA Ops"]
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
        busy = clip(merge((s, e) for _, s, e in ops), lo, hi)
        busy_s += length(busy) / 1e9
        window_s += (hi - lo) / 1e9
        for p in programs:
            runs = [(s, e) for n, s, e in lines.get("XLA Modules", []) if p in n]
            program_runs[p] += [(e - s) / 1e9 for s, e in runs]
            mods = merge(runs)
            program_s[p] += length(intersect(busy, clip(mods, lo, hi))) / 1e9
        for n, s, e in ops:
            if short_name(n).startswith(("while", "conditional")):
                continue  # containers: their bodies' operations are listed themselves
            op_time[short_name(n)] = op_time.get(short_name(n), 0.0) + (e - s) / 1e9
            if COLLECTIVE.search(n.split(" = ")[0]):
                collective_s += (e - s) / 1e9
        idle += gaps(busy, lo, hi)
    n = len(devices)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": busy_s / n,
        "window_s": window_s / n,
        "program_s": {p: v / n for p, v in program_s.items()},
        "program_run_s": {p: sorted(v) for p, v in program_runs.items()},
        "collective_s": collective_s / n,
        "device_ops": [[k, v / n] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[_host_doing(host, s, e), (e - s) / 1e9] for s, e in idle[:10]],
    }


def _host_doing(host, lo, hi) -> str:
    """The shortest host event that covers most of the gap."""
    best = None
    for name, s, e in host:
        if min(e, hi) - max(s, lo) >= 0.5 * (hi - lo) and (best is None or e - s < best[1]):
            best = (name, e - s)
    return short_name(best[0]) if best else "unattributed"


def reduce_dir(trace_dir: str, programs=()) -> dict:
    path = find_xplane(trace_dir)
    return reduce_planes(read_planes(path), programs) if path else {}
