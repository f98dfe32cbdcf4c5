"""Device time by the scopes of a layer-pattern model, and the decode steps
of a traced window (PR 28).

    JAX_PLATFORMS=cpu python -m benchmark.scope_reduce TRACE_DIR    # the reduction as JSON

``span_reduce.py`` sums device time over a fixed list of scope names; a
family whose layers carry other names (``attn_full``, ``attn_window``, ``moe``
with ``moe_router`` inside it, ``dense_mlp``: ``models/mimo_v2.py``) is reduced
here, by the same means: the events through ``span_reduce.read_trace``, an
operation's scope path through ``span_reduce.op_paths``, intervals through
``trace_reduce``. It also counts the decode steps inside the window: every
operation of ``decode_block``'s loop body runs once a step, so the median
number of events over the operations under its ``head`` scope (the head's
product, into which a greedy choice of the next token fuses) is the number
of steps the device ran there; ``prefill_chunk`` has a head of its own, which
the program's name in the path keeps apart.

The readers (``scope_share_pct``, ``traced_decode_steps``) run this as a
process of its own, once a run, and keep its line beside the trace, as
``span_readers.trace_reduction`` does: the harness must not import JAX. A
trace that names none of these scopes (another family's, or the parent
commit's program) reduces to zeros, and the readers return None.
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

from benchmark import span_reduce
from benchmark.trace_reduce import find_xplane, length, merge, short_name

ROOT = Path(__file__).resolve().parent.parent
SCOPES = ("attn_full", "attn_window", "moe", "moe_router", "dense_mlp")
STEP_PROGRAM, STEP_SCOPE = "decode_block", "head"


def segments(path: str) -> set:
    """The names a ``tf_op`` path passes through, each freed of the
    transformations that wrap it (``transpose(jvp(attn))`` is ``attn``)."""
    found = set()
    for seg in path.split(":")[0].split("/"):
        while "(" in seg and seg.endswith(")"):
            seg = seg[seg.index("(") + 1:-1]
        found.add(seg)
    return found


def reduce_ops(devices: list, paths: dict) -> dict:
    """Seconds by scope and the decode steps, averaged over the devices."""
    out = {"busy_s": 0.0, "scope_s": dict.fromkeys(SCOPES, 0.0), "decode_steps": 0.0}
    for ops in devices:
        op_ns, op_events = {}, {}
        for name, start, end in ops:
            op_ns[name] = op_ns.get(name, 0.0) + end - start
            op_events[name] = op_events.get(name, 0) + 1
        out["busy_s"] += length(merge((s, e) for _, s, e in ops)) / 1e9 / len(devices)
        per_step = []
        for name, ns in op_ns.items():
            if short_name(name).startswith(("while", "conditional")):
                continue  # containers: their bodies' operations are listed themselves
            through = segments(paths.get(name, ""))
            for scope in SCOPES:
                if scope in through:
                    out["scope_s"][scope] += ns / 1e9 / len(devices)
            if STEP_PROGRAM in through and STEP_SCOPE in through:
                per_step.append(op_events[name])
        out["decode_steps"] += (statistics.median(per_step) if per_step else 0.0) / len(devices)
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    if not path:
        return {}
    devices, _ = span_reduce.read_trace(path)
    return reduce_ops(devices, span_reduce.op_paths(path)) if devices else {}


# ---- readers (the harness's side: no JAX) ----


def reduction(ctx) -> dict:
    """This run's reduction, computed once and kept in the run's state
    directory; {} where the run left no trace or it does not reduce."""
    state = ROOT / ".benchrun" / ctx["cell"]["name"]
    trace, kept = find_xplane(str(state / "trace")), state / "scope_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.scope_reduce", str(state / "trace")],
                              cwd=ROOT,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"scope_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
        kept.write_text(done.stdout.strip().splitlines()[-1] if done.returncode == 0 and done.stdout.strip() else "{}")
    return json.loads(kept.read_text())


def scope_share_pct(scope):
    """The scope's part of the device's busy time in the traced window."""
    def read(ctx):
        red = reduction(ctx)
        seconds = red.get("scope_s", {}).get(scope, 0.0)
        if not red.get("busy_s") or seconds <= 0.0:
            return None  # the trace names no such scope
        print(f"device s in scope {scope}: {seconds:.6f} of {red['busy_s']:.6f} busy; all: "
              f"{json.dumps({k: round(v, 6) for k, v in red['scope_s'].items()})}", flush=True)
        return 100.0 * seconds / red["busy_s"]
    return read


def traced_decode_steps(ctx):
    return reduction(ctx).get("decode_steps") or None


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
