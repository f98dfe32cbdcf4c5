"""Device time by the scopes of a decoder-hybrid-decoder's cross-decoder,
overall and inside the decode program (PR 36).

    JAX_PLATFORMS=cpu python -m benchmark.xdec_reduce TRACE_DIR    # the reduction as JSON

``scope_reduce.py`` and ``ssm_reduce.py`` sum device time over fixed lists of
scope names; a family whose layers carry further names (``attn_cross``: the
layers that attend ANOTHER layer's keys and values; ``gmu``: the gated memory
units; ``models/phi4_flash.py``) is reduced here, by the same means: the
events through ``span_reduce.read_trace``, an operation's scope path through
``span_reduce.op_paths`` and ``scope_reduce.segments``, intervals through
``trace_reduce``. Besides each scope's seconds over the whole traced window
(``scope_s``) it keeps the seconds of the operations that lie inside
``decode_block`` (``decode_scope_s``), of ``attn_full`` too: the roofline
share of the one slab's eight reads divides by the decode program's
``attn_full`` + ``attn_cross`` seconds, and ``prefill_chunk``'s ``attn_full``
scope (the slab's write) and the head program's (one token a prompt) are
other programs'.

The readers (``scope_share_pct``, ``decode_scope_s``) run this as a process
of its own, once a run, and keep its line beside the trace, as
``ssm_reduce.reduction`` does: the harness must not import JAX. A trace that
names none of these scopes (another family's, or the parent commit's
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
SCOPES = ("attn_cross", "gmu", "attn_full")
DECODE_PROGRAM = "decode_block"


def reduce_ops(devices: list, paths: dict) -> dict:
    """Seconds by scope, over the window and inside the decode program,
    averaged over the devices."""
    out = {"busy_s": 0.0, "scope_s": dict.fromkeys(SCOPES, 0.0), "decode_scope_s": dict.fromkeys(SCOPES, 0.0)}
    for ops in devices:
        op_ns = {}
        for name, start, end in ops:
            op_ns[name] = op_ns.get(name, 0.0) + end - start
        out["busy_s"] += length(merge((s, e) for _, s, e in ops)) / 1e9 / len(devices)
        for name, ns in op_ns.items():
            if short_name(name).startswith(("while", "conditional")):
                continue  # containers: their bodies' operations are listed themselves
            through = segments(paths.get(name, ""))
            for scope in SCOPES:
                if scope in through:
                    out["scope_s"][scope] += ns / 1e9 / len(devices)
                    if DECODE_PROGRAM in through:
                        out["decode_scope_s"][scope] += ns / 1e9 / len(devices)
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
    trace, kept = find_xplane(str(state / "trace")), state / "xdec_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.xdec_reduce", str(state / "trace")], cwd=ROOT,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"xdec_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
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


def decode_scope_s(ctx, *scopes):
    """The scopes' device seconds inside the decode program, summed, or None."""
    inside = reduction(ctx).get("decode_scope_s", {})
    return sum(inside.get(s, 0.0) for s in scopes) or None


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
