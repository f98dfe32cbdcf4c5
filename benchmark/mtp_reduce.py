"""Device time by the scopes of a model that drafts with its own
multi-token-prediction block, overall and inside the decode program (PR 41).

    JAX_PLATFORMS=cpu python -m benchmark.mtp_reduce TRACE_DIR    # the reduction as JSON

``scope_reduce.py``, ``ssm_reduce.py`` and ``xdec_reduce.py`` sum device time
over fixed lists of scope names; a family whose decode step carries further
names (``mtp`` around the block, with ``mtp_attn`` and ``mtp_moe`` inside it:
``models/mimo_v2.py``) is reduced here, by the same means: the events through
``span_reduce.read_trace``, an operation's scope path through
``span_reduce.op_paths`` and ``scope_reduce.segments``, intervals through
``trace_reduce``. Besides each scope's seconds over the whole traced window
(``scope_s``) it keeps the seconds of the operations that lie inside
``decode_block`` (``decode_scope_s``), and there the seconds of the walk's
kernel by its own name (``ops/cache_attention.py``: ``cache_attention_decode``,
the full layer's walk and the block's), which a roofline share of the walk
divides by.

The readers (``scope_share_pct``, ``decode_scope_s``, ``decode_walk_s``) run
this as a process of its own, once a run, and keep its line beside the trace,
as ``ssm_reduce.reduction`` does: the harness must not import JAX. A trace
that names none of these scopes (another family's, or the parent commit's
program) reduces to zeros, and the readers return None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark import span_reduce
from benchmark.scope_reduce import segments
from benchmark.trace_reduce import find_xplane, length, merge, short_name

ROOT = Path(__file__).resolve().parent.parent
SCOPES = ("mtp", "mtp_attn", "mtp_moe", "moe_shared")
DECODE_PROGRAM, WALK_KERNEL = "decode_block", "cache_attention_decode"


def reduce_ops(devices: list, paths: dict) -> dict:
    """Seconds by scope, over the window and inside the decode program, and
    the walk kernel's seconds there, averaged over the devices."""
    out = {"busy_s": 0.0, "scope_s": dict.fromkeys(SCOPES, 0.0), "decode_scope_s": dict.fromkeys(SCOPES, 0.0),
           "decode_walk_s": 0.0, "decode_walk_events": 0.0}
    for ops in devices:
        op_ns, op_events = {}, {}
        for name, start, end in ops:
            op_ns[name] = op_ns.get(name, 0.0) + end - start
            op_events[name] = op_events.get(name, 0) + 1
        out["busy_s"] += length(merge((s, e) for _, s, e in ops)) / 1e9 / len(devices)
        for name, ns in op_ns.items():
            if short_name(name).startswith(("while", "conditional")):
                continue  # containers: their bodies' operations are listed themselves
            path = paths.get(name, "")
            through = segments(path)
            for scope in SCOPES:
                if scope in through:
                    out["scope_s"][scope] += ns / 1e9 / len(devices)
                    if DECODE_PROGRAM in through:
                        out["decode_scope_s"][scope] += ns / 1e9 / len(devices)
            if DECODE_PROGRAM in through and (WALK_KERNEL in path or WALK_KERNEL in name):
                out["decode_walk_s"] += ns / 1e9 / len(devices)
                out["decode_walk_events"] += op_events[name] / len(devices)
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
    trace, kept = find_xplane(str(state / "trace")), state / "mtp_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.mtp_reduce", str(state / "trace")], cwd=ROOT,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"mtp_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
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
              f"{json.dumps({k: round(v, 6) for k, v in red['scope_s'].items()})}; inside {DECODE_PROGRAM}: "
              f"{json.dumps({k: round(v, 6) for k, v in red['decode_scope_s'].items()})}", flush=True)
        return 100.0 * seconds / red["busy_s"]
    return read


def decode_walk_s(ctx):
    """(the walk kernel's device seconds inside the decode program, its
    events there), or (None, None)."""
    red = reduction(ctx)
    return (red.get("decode_walk_s") or None), (red.get("decode_walk_events") or None)


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
