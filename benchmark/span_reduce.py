"""The program's own names in a ``jax.profiler`` trace: device time by scope,
and idle gaps by the program span the host was in.

    JAX_PLATFORMS=cpu python -m benchmark.span_reduce TRACE_DIR    # the reduction as JSON

Run so, as a process of its own, by ``span_readers.trace_reduction`` (the
harness must not import JAX). Events come through ``jax.profiler.ProfileData``
and the intervals through ``trace_reduce``'s own routines, as that module's
numbers do. One thing ``ProfileData`` does not show: the scope path of a
device operation (``jax.named_scope`` and Flax's module names:
``jit(train_step)/jvp(Llama)/layers/attn/q_proj/dot_general``) is the
``tf_op`` stat of the event's *metadata*, not of the event, so ``op_paths``
reads that one map from the file's wire format.

- (a) ``scope_s``: for each known scope, the time of the ``XLA Ops`` events
  whose path passes through it (a part of the busy time; scopes nest, so their
  sum is not the whole). ``while`` and ``conditional`` hold their bodies'
  operations, which are listed themselves, and are left out as in
  ``trace_reduce``.
- (b) ``idle``: every gap of at least 0.5 ms between device operations, split
  over the program spans (``obs.span`` mirrors: ``engine.*``, ``serve.*``,
  ``step`` ...) by the innermost one open at each instant; what no program
  span covers is ``outside``.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

from benchmark.trace_reduce import find_xplane, gaps, length, merge, short_name

GAP_MIN_NS = 0.5e6
# Scopes the program names (models/llama.py, ops/, workloads/trainer.py, serving/engine.py).
SCOPES = ("embed", "layers", "attn", "mlp", "moe_mlp", "kv_quantize", "kv_dequantize", "final_norm",
          "lm_head", "head", "sample", "loss", "optimizer")
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
PROGRAM_SPAN = re.compile(r"^(engine|serve)\.[a-z_]+$|^(step|save|feed_produce|feed_put|feed_wait|ckpt_[a-z_]+)$")


# ---- what ProfileData hides: an operation's scope path ----


def _varint(buf, pos: int):
    val = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, value) of one protocol-buffer message: a varint as an
    int, a length-delimited field as its (start, end) in ``buf``, a fixed one
    as None."""
    while pos < end:
        key, pos = _varint(buf, pos)
        wire, val = key & 7, None
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val = (pos, pos + n)
            pos += n
        elif wire in (1, 5):
            pos += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}: not an xplane file")
        yield key >> 3, val


def op_paths(path: str) -> dict:
    """{operation name: scope path} from the event metadata of the device
    planes. XSpace: planes 1. XPlane: name 2, lines 3, event_metadata 4,
    stat_metadata 5 (maps: an entry's value is its field 2). X*Metadata: id 1,
    name 2, stats 5. XStat: metadata_id 1, str_value 5, ref_value 7 (the name
    of that stat metadata)."""
    buf = Path(path).read_bytes()

    def text(span) -> str:
        return buf[span[0]:span[1]].decode("utf-8", "replace")

    def value(entry) -> list:
        span = dict(_fields(buf, *entry)).get(2)
        return list(_fields(buf, *span)) if span else []

    out = {}
    for no, plane in _fields(buf, 0, len(buf)):
        top = list(_fields(buf, *plane)) if no == 1 else []
        if not any(n == 2 and text(v).startswith("/device:TPU") for n, v in top):
            continue
        stat_names = {}
        for meta in (dict(value(v)) for n, v in top if n == 5):
            stat_names[meta.get(1)] = text(meta[2]) if 2 in meta else ""
        for meta in (value(v) for n, v in top if n == 4):
            op = found = ""
            for n, v in meta:
                if n == 2:
                    op = text(v)
                elif n == 5:
                    stat = dict(_fields(buf, *v))
                    if stat_names.get(stat.get(1)) == "tf_op":
                        found = text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            if op and found:
                out[op] = found
    return out


def read_trace(path: str):
    """(per device: its ``XLA Ops`` events, the program's spans on the host),
    each event ``(name, start ns, end ns)`` on the profiler's one clock."""
    from jax.profiler import ProfileData

    devices, spans, names = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                # A window holds millions of events under a few thousand names: keep one string of each.
                ops = [(names.setdefault(n, n), ev.start_ns, ev.end_ns) for ev in line.events for n in (ev.name,)]
                if ops:
                    devices.append(ops)
        elif plane.name == "/host:CPU":
            spans += [(ev.name, ev.start_ns, ev.end_ns) for line in plane.lines for ev in line.events
                      if PROGRAM_SPAN.match(ev.name)]
    return devices, spans


# ---- (a) device time by scope ----


def scopes_of(path: str) -> set:
    """The known scopes a ``tf_op`` path passes through. A segment may be
    wrapped by a transformation: ``transpose(jvp(attn))`` is ``attn``."""
    found = set()
    for seg in path.split(":")[0].split("/"):
        inner = seg
        while "(" in inner and inner.endswith(")"):
            inner = inner[inner.index("(") + 1:-1]
        if inner in SCOPES:
            found.add(inner)
    return found


def by_scope(ops, paths: dict) -> dict:
    """One device's operations -> seconds by scope and by kernel."""
    op_ns: dict = {}
    for name, start, end in ops:
        op_ns[name] = op_ns.get(name, 0.0) + end - start
    scope_s, kernel_s, named = dict.fromkeys(SCOPES, 0.0), dict.fromkeys(KERNELS, 0.0), 0.0
    for name, ns in op_ns.items():
        if short_name(name).startswith(("while", "conditional")):
            continue  # containers: their bodies' operations are listed themselves
        path = paths.get(name, "")
        found = scopes_of(path)
        named += ns / 1e9 if found else 0.0
        for scope in found:
            scope_s[scope] += ns / 1e9
        for kernel in KERNELS:
            if kernel in name or kernel in path:
                kernel_s[kernel] += ns / 1e9
    return {"scope_s": scope_s, "kernel_s": kernel_s, "in_a_scope_s": named}


# ---- (b) idle gaps by program span ----


def innermost(spans, lo: float, hi: float) -> dict:
    """[lo, hi] split over ``spans`` (name, start, end): each instant goes to
    the span that started last among those open; the rest is ``outside``."""
    open_ = [(s, e, n) for n, s, e in spans if s < hi and e > lo]
    edges = sorted({lo, hi, *(x for s, e, _ in open_ for x in (s, e) if lo < x < hi)})
    out: dict = {}
    for a, b in zip(edges, edges[1:]):
        cover = [(s, -e, n) for s, e, n in open_ if s <= a and e >= b]
        name = max(cover)[2] if cover else "outside"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce_trace(devices: list, spans: list, paths: dict) -> dict:
    """Times in seconds, averaged over the devices as ``trace_reduce`` does;
    the idle gaps are summed over them."""
    if not devices:
        return {}
    n = len(devices)
    out = {"busy_s": 0.0, "window_s": 0.0, "in_a_scope_s": 0.0,
           "scope_s": dict.fromkeys(SCOPES, 0.0), "kernel_s": dict.fromkeys(KERNELS, 0.0)}
    idle = {"gaps": 0, "gap_s": 0.0, "by_span_s": {}, "largest": []}
    spans_in_window: dict = {}
    for ops in devices:
        lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
        busy = merge((s, e) for _, s, e in ops)
        out["busy_s"] += length(busy) / 1e9 / n
        out["window_s"] += (hi - lo) / 1e9 / n
        scoped = by_scope(ops, paths)
        out["in_a_scope_s"] += scoped["in_a_scope_s"] / n
        for key in ("scope_s", "kernel_s"):
            for name, sec in scoped[key].items():
                out[key][name] += sec / n
        for a, b in gaps(busy, lo, hi):
            if b - a < GAP_MIN_NS:
                continue
            split = innermost(spans, a, b)
            idle["gaps"] += 1
            idle["gap_s"] += (b - a) / 1e9
            for name, ns in split.items():
                idle["by_span_s"][name] = idle["by_span_s"].get(name, 0.0) + ns / 1e9
            idle["largest"].append([(b - a) / 1e6, {name: ns / 1e6 for name, ns in split.items()}])
        for name, start, end in spans:
            if lo <= start and end <= hi:
                spans_in_window[name] = spans_in_window.get(name, 0) + 1
    idle["largest"] = sorted(idle["largest"], key=lambda g: -g[0])[:10]
    out["idle"] = idle
    out["spans_in_window"] = spans_in_window
    out["scope_stat"] = "tf_op" if out["in_a_scope_s"] > 0 else None
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    return reduce_trace(*read_trace(path), op_paths(path)) if path else {}


def table(red: dict) -> str:
    """The idle gaps of at least 0.5 ms by program span, as lines to print."""
    idle = red.get("idle")
    if not idle:
        return "no device trace to reduce"
    total = red["window_s"] - red["busy_s"]
    rows = [f"idle gaps >= 0.5 ms: {idle['gaps']} gaps, {1e3 * idle['gap_s']:.3f} ms of "
            f"{1e3 * total:.3f} ms idle in a window of {1e3 * red['window_s']:.3f} ms"]
    for name, sec in sorted(idle["by_span_s"].items(), key=lambda kv: -kv[1]):
        rows.append(f"  {name:28s} {1e3 * sec:10.3f} ms  {100 * sec / idle['gap_s']:5.1f}%")
    for ms, split in idle["largest"]:
        parts = ", ".join(f"{n} {v:.2f}" for n, v in sorted(split.items(), key=lambda kv: -kv[1]))
        rows.append(f"  gap {ms:8.3f} ms: {parts}")
    return "\n".join(rows)


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(table(red), file=sys.stderr)
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
