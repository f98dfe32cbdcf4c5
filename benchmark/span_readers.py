"""Readers of the per-layer metrics that read what the program measures
inside itself (PR 24): the engine's counters in the final ``metrics`` record,
the three parts of each response's time to first token, and the program's
names in the device trace (``span_reduce.py``). Like ``layer_readers.py``: a
reader takes the run's context and returns a number, or None where it finds
nothing to read — a program without the counter, the field or the name (the
parent commit) — and never raises for that."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark import span_reduce

ROOT = Path(__file__).resolve().parent.parent


def final_value(key):
    """A number of the replica's final ``metrics`` record (``engine.stats()``)."""
    def read(ctx):
        value = ctx.get("final", {}).get(key)
        return value if isinstance(value, (int, float)) else None
    return read


def trace_reduction(ctx) -> dict:
    """``span_reduce`` of this run's trace, computed once, in a process of
    its own (it reads the trace with JAX's ``ProfileData``; this one must not
    import JAX), and kept beside the trace in the run's state directory; {}
    where the run left no trace or the trace does not reduce."""
    state = ROOT / ".benchrun" / ctx["cell"]["name"]
    trace, kept = span_reduce.find_xplane(str(state / "trace")), state / "span_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.span_reduce", str(state / "trace")],
                              cwd=Path(span_reduce.__file__).resolve().parent.parent,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"span_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
        kept.write_text(done.stdout.strip().splitlines()[-1] if done.returncode == 0 else "{}")
    return json.loads(kept.read_text())


def host_gap_ms_per_s(ctx):
    """Host milliseconds between a fence's return and the next dispatch, while
    the engine had work, per second of the window (PR 25 let the engine choose
    how many dispatches a run makes, so a quotient over them compares across no
    PR). Its segments, the cross-check against the trace's idle time and the
    table of idle gaps by program span go on earlier lines."""
    final = ctx.get("final", {})
    gap, seconds = final.get("host_gap_s"), ctx.get("seconds")
    if gap is None or not seconds:
        return None
    per_s = 1e3 * gap / seconds
    # The gap's parts are the record's ``host_gap_<segment>_s``; its other ``host_<segment>_s`` are the
    # rest of the serving thread's time (blocked in a fence, dispatching, idle).
    parts = {k[9:-2]: round(1e3 * v / seconds, 3) for k, v in final.items()
             if k.startswith("host_gap_") and k.endswith("_s") and k != "host_gap_s"}
    rest = {k[5:-2]: round(1e3 * v / seconds, 3) for k, v in final.items()
            if k.startswith("host_") and k.endswith("_s") and not k.startswith("host_gap_")}
    blocks = final.get("decode_blocks")
    print(f"host gap ms per s of a {seconds:g} s window, {blocks} blocks: {json.dumps(parts)}; the rest of the serving "
          f"thread's time, ms per s: {json.dumps(rest)}", flush=True)
    rounds, admitted = final.get("admit_rounds"), final.get("admitted")
    if rounds and admitted and blocks:
        print(f"admissions: {admitted} in {rounds} rounds ({admitted / rounds:.3f} a round, {rounds / blocks:.3f} rounds "
              f"a block); prefill chunks {final.get('prefill_chunks', 0)} ({final.get('prefill_chunks', 0) / admitted:.3f} "
              f"an admission)", flush=True)
    red = trace_reduction(ctx)
    if red:
        print(f"two clocks: host gap {per_s:.3f} ms/s x {red['window_s']:.3f} s of traced window = "
              f"{per_s * red['window_s']:.3f} ms; the trace's idle time {1e3 * (red['window_s'] - red['busy_s']):.3f} ms",
              flush=True)
        print(span_reduce.table(red), flush=True)
    return per_s


def scope_share_pct(*scopes):
    """The named scopes' part of the device's busy time in the traced window."""
    def read(ctx):
        red = trace_reduction(ctx)
        if not red or not red.get("busy_s"):
            return None
        seconds = sum(red["scope_s"].get(s, 0.0) for s in scopes)
        if seconds <= 0.0:
            return None  # the trace names no such scope
        kernels = {k: round(v, 6) for k, v in red["kernel_s"].items() if v}
        print(f"device s by scope {'+'.join(scopes)}: {seconds:.6f} of {red['busy_s']:.6f} busy "
              f"({red['in_a_scope_s']:.6f} s in any scope){'; kernels ' + json.dumps(kernels) if kernels else ''}",
              flush=True)
        return 100.0 * seconds / red["busy_s"]
    return read
