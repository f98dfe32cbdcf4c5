"""The prefill programs of a traced window, and the walk's kernel inside the
decode program (PR 46): one pass over the trace for the three device sums
that the long-document cell's readers divide.

    JAX_PLATFORMS=cpu python -m benchmark.prefill_reduce TRACE_DIR    # the reduction as JSON

- ``prefill_s``: the device's busy time inside the runs of the programs named
  ``jit_prefill_chunk...`` (the chunk's and the head's: ``XLA Modules``), as
  ``trace_reduce`` takes a program's time; ``chunk_runs`` / ``head_runs``
  count those runs;
- ``prefill_scope_s``: of it, by scope (``ssm``, ``ssm_conv``, ``ssm_scan``,
  ``attn_full``, ``dense_mlp``): the operations whose scope path passes
  through the scope AND a prefill program (``span_reduce.op_paths``,
  ``scope_reduce.segments``);
- ``spans``: the ``engine.prefill_dispatch`` spans the host opened inside the
  device's window, with what each said (``serving/engine.py``): how many, the
  prompt tokens they carried (``n_real``), the sum of those tokens' positions
  (a chunk at ``start`` holds positions ``start .. start + n_real - 1``), how
  many queued the head behind them and how many resumed a prompt begun at an
  earlier boundary;
- ``decode_walk_s`` / ``decode_walk_events``: the seconds and runs of the
  kernel named ``cache_attention_decode`` inside ``decode_block``, as
  ``mtp_reduce`` takes them (folded in here: each reducer is one more pass
  over the trace inside a run's time limit).

The readers run this as a process of its own, once a run, and keep its line
beside the trace, as ``ssm_reduce.reduction`` does: the harness must not
import JAX. A trace without these programs, scopes or span arguments (another
family's, or the parent commit's program) reduces to zeros, and the readers
return None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from benchmark import span_reduce
from benchmark.scope_reduce import segments
from benchmark.trace_reduce import clip, find_xplane, intersect, length, merge, short_name

ROOT = Path(__file__).resolve().parent.parent
PREFILL, HEAD = "prefill_chunk", "prefill_chunk_head"
SCOPES = ("ssm", "ssm_conv", "ssm_scan", "attn_full", "dense_mlp")
DECODE_PROGRAM, WALK_KERNEL = "decode_block", "cache_attention_decode"
CHUNK_SPAN = "engine.prefill_dispatch"


def read_trace(path: str):
    """(per device: its ``XLA Ops`` events and its ``XLA Modules`` events,
    the chunk spans on the host with their arguments)."""
    from jax.profiler import ProfileData

    devices, spans, names = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(names.setdefault(n, n), ev.start_ns, ev.end_ns) for ev in line.events for n in (ev.name,)]
                elif line.name == "XLA Modules":
                    mods = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events]
            if ops:
                devices.append((ops, mods))
        elif plane.name == "/host:CPU":
            spans += [(ev.start_ns, dict(ev.stats)) for line in plane.lines for ev in line.events if ev.name == CHUNK_SPAN]
    return devices, spans


def reduce_events(devices: list, spans: list, paths: dict) -> dict:
    out = {"busy_s": 0.0, "prefill_s": 0.0, "chunk_runs": 0.0, "head_runs": 0.0,
           "prefill_scope_s": dict.fromkeys(SCOPES, 0.0), "decode_walk_s": 0.0, "decode_walk_events": 0.0}
    n = len(devices)
    lo = min(s for ops, _ in devices for _, s, _ in ops)
    hi = max(e for ops, _ in devices for _, _, e in ops)
    for ops, mods in devices:
        busy = clip(merge((s, e) for _, s, e in ops), lo, hi)
        out["busy_s"] += length(busy) / 1e9 / n
        runs = [(name, s, e) for name, s, e in mods if PREFILL in name]
        out["prefill_s"] += length(intersect(busy, clip(merge((s, e) for _, s, e in runs), lo, hi))) / 1e9 / n
        out["head_runs"] += sum(HEAD in name for name, _, _ in runs) / n
        out["chunk_runs"] += sum(HEAD not in name for name, _, _ in runs) / n
        op_ns, op_events = {}, {}
        for name, start, end in ops:
            op_ns[name] = op_ns.get(name, 0.0) + end - start
            op_events[name] = op_events.get(name, 0) + 1
        for name, ns in op_ns.items():
            if short_name(name).startswith(("while", "conditional")):
                continue  # containers: their bodies' operations are listed themselves
            path = paths.get(name, "")
            through = segments(path)
            if PREFILL in through or HEAD in through:
                for scope in SCOPES:
                    if scope in through:
                        out["prefill_scope_s"][scope] += ns / 1e9 / n
            if DECODE_PROGRAM in through and (WALK_KERNEL in path or WALK_KERNEL in name):
                out["decode_walk_s"] += ns / 1e9 / n
                out["decode_walk_events"] += op_events[name] / n
    said = [a for at, a in spans if lo <= at <= hi]
    if said and all(k in a for a in said for k in ("start", "n_real", "head")):
        out["spans"] = {
            "chunks": len(said), "n_real": sum(a["n_real"] for a in said),
            "position_sum": sum(a["n_real"] * (a["start"] + (a["n_real"] - 1) / 2.0) for a in said),
            "heads": sum(int(a["head"]) for a in said), "resumed": sum(int(a.get("resumed", 0)) for a in said),
        }
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    if not path:
        return {}
    devices, spans = read_trace(path)
    return reduce_events(devices, spans, span_reduce.op_paths(path)) if devices else {}


# ---- readers (the harness's side: no JAX) ----


def reduction(ctx) -> dict:
    """This run's reduction, computed once and kept in the run's state
    directory; {} where the run left no trace or it does not reduce."""
    state = ROOT / ".benchrun" / ctx["cell"]["name"]
    trace, kept = find_xplane(str(state / "trace")), state / "prefill_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.prefill_reduce", str(state / "trace")], cwd=ROOT,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"prefill_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
        kept.write_text(done.stdout.strip().splitlines()[-1] if done.returncode == 0 and done.stdout.strip() else "{}")
    return json.loads(kept.read_text())


def prefill_tokens(red: dict):
    """(the prompt tokens the window's chunk runs carried, their mean
    position, the heads that ran), or None: the spans' ``n_real`` a chunk
    times the runs of the chunk's program on the device there (the host is a
    few programs ahead of the device, so the spans of a window and its runs
    are not the same chunks at the edges; their mean is the same)."""
    spans = red.get("spans")
    if not spans or not spans["chunks"] or not spans["n_real"] or not red.get("chunk_runs"):
        return None
    return (spans["n_real"] / spans["chunks"] * red["chunk_runs"], spans["position_sum"] / spans["n_real"],
            red.get("head_runs", 0.0))


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
