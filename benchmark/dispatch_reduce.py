"""The dispatches of a traced window (PR 39): each run of ``decode_block`` on
the device paired with the engine's dispatch that queued it, and the serve
loop's boundaries between them.

    JAX_PLATFORMS=cpu python -m benchmark.dispatch_reduce TRACE_DIR    # the reduction as JSON

The engine's spans carry what each dispatch was (``engine.decode_dispatch``:
``rows``, ``steps``, ``sized_by``; ``engine.decode_fence``: those and ``live``,
``attended``; ``engine.prefill_dispatch``: ``n_real``, ``head``;
``engine.first_token``: ``n``), as ``TraceAnnotation`` s in the profiler's own
trace, so the counts of exactly the traced seconds are here, beside the
device's runs of the program: a step's device time is the runs' time over the
dispatches' steps, and a fence's tail is what the host still waited after
its run had ended. The serve loop is serial (at most one decode dispatch in
flight), so the k-th run is the k-th dispatch's; only the ends of the trace
are in doubt (a run whose annotation opened before the trace began, a
dispatch whose fence returned after it ended). The device's events and the
host's are stamped by two clocks that the profiler brings together to within
about a millisecond, which is more than a tiny program runs, so the runs are
not matched to the spans by time alone: of the few ways to line the two
sequences up at the head, the one is taken under which ONE shift of the
device's clock puts every run inside its dispatch (after the dispatch's span
opens, before its fence returns), and among those the smallest shift; one
that needs more than 5 ms is no pairing (dispatches a regular period apart
fit each other's runs under a shift of one period).

A trace of a program without these spans or arguments (the parent commit)
reduces to {}, and every reader returns None. The readers run this as a
process of its own, once a run, as ``span_readers.trace_reduction`` runs
``span_reduce``: the harness must not import JAX.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import subprocess
import sys
from pathlib import Path

from benchmark.trace_reduce import find_xplane

ROOT = Path(__file__).resolve().parent.parent
PROGRAM = "decode_block"
DISPATCH, FENCE, CHUNK, FIRST = ("engine.decode_dispatch", "engine.decode_fence", "engine.prefill_dispatch",
                                 "engine.first_token")
STEP, BOUNDARY, IDLE = "engine.step", "serve.boundary", "serve.idle"
PHASES = ("serve.respond", "serve.poll", "serve.submit")   # the loop's own, inside a boundary
AFTER_FENCE = ("engine.accept", "engine.harvest")           # the engine's, between a fence and the step's end
HEAD_SHIFTS = (0, 1, -1, 2, -2)   # runs (+) or dispatches (-) left over at the head of the trace
CLOCKS_APART_NS = 5e6             # the most the two clocks are taken to disagree by: beyond it a pairing is wrong


def read_trace(path: str):
    """(the runs of ``decode_block`` on the first device that has any, the
    device's window, the program's spans on the host): a run ``(start ns, end
    ns)``, a span ``(name, start ns, end ns, arguments)``."""
    from jax.profiler import ProfileData

    wanted = {DISPATCH, FENCE, CHUNK, FIRST, STEP, BOUNDARY, IDLE, *PHASES, *AFTER_FENCE}
    runs, window, spans = [], None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU") and not runs:
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                mods = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
                if mods:
                    window = (min(s for _, s, _ in mods), max(e for _, _, e in mods))
                    runs = sorted((s, e) for n, s, e in mods if PROGRAM in n)
        elif plane.name == "/host:CPU":
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
                      for line in plane.lines for ev in line.events if ev.name in wanted]
    return runs, window, sorted(spans, key=lambda s: s[1])


def dispatches_of(spans) -> list:
    """The decode dispatches whose fence is in the trace, in order: the
    dispatch's span opening, its fence's return, the fence's arguments and
    the rule that sized it."""
    out = []
    heads = [s for s in spans if s[0] == DISPATCH]
    fences = [s for s in spans if s[0] == FENCE]
    starts = [f[1] for f in fences]
    for i, (_, d0, d1, args) in enumerate(heads):
        k = bisect.bisect_left(starts, d1)
        nxt = heads[i + 1][1] if i + 1 < len(heads) else float("inf")
        if k == len(fences) or fences[k][1] >= nxt:
            continue  # the trace ended before this dispatch's fence returned
        f = fences[k]
        if not all(key in f[3] for key in ("rows", "steps", "live", "attended")):
            return []  # a program whose fence says nothing: the parent commit
        out.append({"opened": d0, "fenced": f[2], **f[3], "sized_by": args.get("sized_by")})
    return out


def pair(runs: list, dispatches: list):
    """(pairs of (run, dispatch), the device clock's shift in ns that the
    pairing needs at least): see the module's text. ([], None) where no way
    of lining the two up puts every run inside its dispatch."""
    best = None
    for shift in HEAD_SHIFTS:
        rs, ds = (runs[shift:], dispatches) if shift >= 0 else (runs, dispatches[-shift:])
        pairs = list(zip(rs, ds))
        if not pairs:
            continue
        lo = max(d["opened"] - r[0] for r, d in pairs)    # the shift must be at least this ...
        hi = min(d["fenced"] - r[1] for r, d in pairs)    # ... and at most this
        if lo > hi:
            continue
        need = 0.0 if lo <= 0.0 <= hi else (lo if lo > 0.0 else hi)
        if abs(need) <= CLOCKS_APART_NS and (best is None or abs(need) < abs(best[1])):
            best = (pairs, need)
    return best or ([], None)


def inside(spans, name, lo, hi) -> list:
    return [s for s in spans if s[0] == name and lo <= s[1] and s[2] <= hi]


def reduce_events(runs: list, window, spans: list) -> dict:
    dispatches = dispatches_of(spans)
    pairs, shift = pair(runs, dispatches)
    if not pairs or window is None:
        return {}
    lo, hi = window
    tails = [d["fenced"] - r[1] for r, d in pairs]
    out = {
        "window_s": (hi - lo) / 1e9, "runs": len(runs), "dispatches": len(pairs), "clock_shift_ms": shift / 1e6,
        "steps": sum(d["steps"] for _, d in pairs), "rows": sum(d["rows"] for _, d in pairs),
        "row_steps": sum(d["rows"] * d["steps"] for _, d in pairs),
        "live": sum(d["live"] for _, d in pairs), "attended": sum(d["attended"] for _, d in pairs),
        # A row that takes every step of its dispatch has one more position live at each.
        "live_steps": sum(d["steps"] * d["live"] + d["rows"] * d["steps"] * (d["steps"] - 1) // 2 for _, d in pairs),
        "device_s": sum(r[1] - r[0] for r, _ in pairs) / 1e9,
        "fence_tail_s": sum(tails) / 1e9, "fence_tail_p50_s": statistics.median(tails) / 1e9,
        "fence_tail_max_s": max(tails) / 1e9,
        "sized_by": {},
    }
    for _, d in pairs:
        out["sized_by"][str(d["sized_by"])] = out["sized_by"].get(str(d["sized_by"]), 0) + 1
    # A dispatch with no chunk queued in front of it: from its span's opening to its fence's return the host saw
    # the launch, the run and the tail, and the run's own length is the device's: the rest needs no second clock.
    chunk_starts = [s[1] for s in spans if s[0] == CHUNK]
    steps = [s for s in spans if s[0] == STEP]
    step_starts = [s[1] for s in steps]
    alone = []
    for r, d in pairs:
        k = bisect.bisect_right(step_starts, d["opened"]) - 1
        if k < 0 or steps[k][2] < d["fenced"]:
            continue  # its ``engine.step`` opened before the trace: what was queued in front is not known
        if bisect.bisect_left(chunk_starts, step_starts[k]) == bisect.bisect_left(chunk_starts, d["opened"]):
            alone.append((d["fenced"] - d["opened"]) - (r[1] - r[0]))
    out["round_trip_less_run_s"] = sum(alone) / 1e9
    out["dispatches_alone"] = len(alone)
    # The prefill of the window, from its chunks' own spans.
    chunks = [s[3] for s in inside(spans, CHUNK, lo, hi)]
    if chunks and all("n_real" in c and "head" in c for c in chunks):
        out["prefill"] = {"chunks": len(chunks), "n_real": sum(c["n_real"] for c in chunks),
                          "heads": sum(int(c["head"]) for c in chunks),
                          "first_tokens": sum(s[3].get("n", 0) for s in inside(spans, FIRST, lo, hi))}
    # The serve loop's side of a boundary, and the engine's around it.
    bounds = inside(spans, BOUNDARY, lo, hi)
    if bounds:
        edges = [b[1] for b in bounds]
        parts = dict.fromkeys(PHASES, 0.0)
        for name, s, e, _ in spans:
            k = bisect.bisect_right(edges, s) - 1
            if name in parts and k >= 0 and e <= bounds[k][2]:
                parts[name] += (e - s) / 1e9
        total = sum(b[2] - b[1] for b in bounds) / 1e9
        out["boundary"] = {"count": len(bounds), "total_s": total, "max_s": max(b[2] - b[1] for b in bounds) / 1e9,
                           "parts_s": parts, "self_s": total - sum(parts.values())}
    # The host's side of the gaps, on the profiler's clock: from a fence's return to the next dispatch's span,
    # while the engine was busy (no ``serve.idle`` between them).
    opens = sorted(s[1] for s in spans if s[0] in (DISPATCH, CHUNK))
    idles = [s[1] for s in spans if s[0] == IDLE]
    gap_s, gaps = 0.0, 0
    for _, _, fenced, _ in inside(spans, FENCE, lo, hi):
        k = bisect.bisect_left(opens, fenced)
        if k < len(opens) and opens[k] <= hi and bisect.bisect_left(idles, fenced) == bisect.bisect_left(idles, opens[k]):
            gap_s += (opens[k] - fenced) / 1e9
            gaps += 1
    out["host_gaps"] = gaps
    out["host_gap_s"] = gap_s
    out["after_fence_s"] = {n: sum(s[2] - s[1] for s in inside(spans, n, lo, hi)) / 1e9 for n in AFTER_FENCE}
    return out


def reduce_dir(trace_dir: str) -> dict:
    path = find_xplane(trace_dir)
    return reduce_events(*read_trace(path)) if path else {}


def table(red: dict, chunk: int = 0) -> str:
    """The reduction as lines to print; with the engine's ``chunk`` (tokens
    a prefill call) also the window's share of pad tokens."""
    if not red:
        return "no dispatch to pair in this trace"
    d, steps = red["dispatches"], red["steps"]
    rows = [
        f"dispatches paired: {d} of {red['runs']} runs of {PROGRAM} in a window of {1e3 * red['window_s']:.3f} ms "
        f"(the device's clock shifted by at least {red['clock_shift_ms']:.3f} ms to lie inside them); sized by "
        f"{json.dumps(red['sized_by'])}",
        f"  steps {steps}, rows x steps {red['row_steps']} ({red['row_steps'] / steps:.2f} rows a step), live at the "
        f"first steps {red['live']} ({red['live'] / red['rows']:.1f} a row), live over the steps if every row took "
        f"each {red['live_steps']} ({red['live_steps'] / red['row_steps']:.1f} a row-step), attended {red['attended']} "
        f"({red['attended'] / red['live_steps']:.3f} of live)",
        f"  device {1e3 * red['device_s']:.3f} ms = {1e3 * red['device_s'] / steps:.4f} ms a step; fence tails "
        f"{1e3 * red['fence_tail_s']:.3f} ms = {1e3 * red['fence_tail_s'] / d:.4f} a dispatch (median "
        f"{1e3 * red['fence_tail_p50_s']:.4f}, the longest {1e3 * red['fence_tail_max_s']:.3f})",
    ]
    if red["dispatches_alone"]:
        rows.append(f"  round trip less the run (one clock each; dispatches with no chunk in front): "
                    f"{1e3 * red['round_trip_less_run_s'] / red['dispatches_alone']:.4f} ms over {red['dispatches_alone']}")
    pre = red.get("prefill")
    if pre:
        pads = f" = {100.0 * (1.0 - pre['n_real'] / (pre['chunks'] * chunk)):.3f}% pads" if chunk else ""
        rows.append(f"  prefill: {pre['chunks']} chunks, {pre['n_real']} prompt tokens{pads}, {pre['heads']} heads, "
                    f"{pre['first_tokens']} first tokens read")
    b = red.get("boundary")
    if b:
        parts = ", ".join(f"{n} {1e3 * v / b['count']:.4f}" for n, v in b["parts_s"].items())
        rows.append(f"  serve.boundary: {b['count']} of mean {1e3 * b['total_s'] / b['count']:.4f} ms (the longest "
                    f"{1e3 * b['max_s']:.3f}): {parts}, self {1e3 * b['self_s'] / b['count']:.4f}")
    after = ", ".join(f"{n} {1e3 * v:.3f}" for n, v in red["after_fence_s"].items())
    rows.append(f"  the host's side of {red['host_gaps']} gaps (a fence's return to the next dispatch's span, engine busy): "
                f"{1e3 * red['host_gap_s']:.3f} ms, of which serve.boundary {1e3 * (b or {}).get('total_s', 0.0):.3f}, "
                f"{after}")
    return "\n".join(rows)


# ---- readers (the harness's side: no JAX) ----


def reduction(ctx) -> dict:
    """This run's reduction, computed once, in a process of its own, and
    kept beside the trace in the run's state directory; {} where the run
    left no trace or the trace has no dispatch to pair."""
    state = ROOT / ".benchrun" / ctx["cell"]["name"]
    trace, kept = find_xplane(str(state / "trace")), state / "dispatch_reduce.json"
    if not trace:
        return {}
    if not (kept.is_file() and kept.stat().st_mtime >= Path(trace).stat().st_mtime):
        done = subprocess.run([sys.executable, "-m", "benchmark.dispatch_reduce", str(state / "trace")],
                              cwd=Path(__file__).resolve().parent.parent,
                              env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        if done.returncode != 0 and done.stderr.strip():
            print(f"dispatch_reduce: rc {done.returncode}: {done.stderr.strip()[-400:]}", flush=True)
        kept.write_text(done.stdout.strip().splitlines()[-1] if done.returncode == 0 and done.stdout.strip() else "{}")
    return json.loads(kept.read_text())


def decode_step_ms(ctx):
    """Device time of the paired runs of ``decode_block`` over the steps of
    their dispatches; the whole reduction, and the same counts from the
    engine's whole record and from ``scope_reduce``, go on earlier lines."""
    from benchmark.scope_reduce import traced_decode_steps

    red = reduction(ctx)
    if not red.get("steps") or not red.get("device_s"):
        return None
    chunk = int(ctx.get("config", {}).get("bench", {}).get("engine", {}).get("chunk", 0))
    print(table(red, chunk), flush=True)
    final, by_scope = ctx.get("final", {}), traced_decode_steps(ctx)
    if by_scope:
        print(f"decode steps of the traced window: {red['steps']} by the paired dispatches' spans, {by_scope:g} by "
              f"scope_reduce's count of the loop body's events", flush=True)
    if final.get("decode_steps") and final.get("decode_tokens") and final.get("decode_live_positions"):
        print(f"the whole record's means beside them: {final['decode_row_steps'] / final['decode_steps']:.2f} rows a "
              f"step, {final['decode_live_positions'] / final['decode_tokens']:.1f} live positions a token, attended "
              f"{final.get('decode_attended_positions', 0) / final['decode_live_positions']:.3f} of live, pads "
              f"{final.get('prefill_pad_pct')}%", flush=True)
    return 1e3 * red["device_s"] / red["steps"]


def fence_tail_ms(ctx):
    """Mean over the window's paired dispatches of the fence's return less
    the end of its run on the device (two clocks: the shift the pairing
    needed is on ``decode_step_ms``'s lines)."""
    red = reduction(ctx)
    if not red.get("dispatches"):
        return None
    alone = red.get("dispatches_alone")
    print(f"fence tails over {red['dispatches']} dispatches: median {1e3 * red['fence_tail_p50_s']:.4f} ms, the longest "
          f"{1e3 * red['fence_tail_max_s']:.3f} (a pause of the machine inside a fence is in the mean)"
          + (f"; a dispatch's round trip on the host less its run on the device, no chunk in front: "
             f"{1e3 * red['round_trip_less_run_s'] / alone:.4f} ms over {alone} dispatches" if alone else ""), flush=True)
    return 1e3 * red["fence_tail_s"] / red["dispatches"]


def boundary_ms(ctx):
    """Mean duration of ``serve.boundary`` in the traced window; its
    children's parts, its self time and the gaps' sum against the trace's idle
    time on the lines before."""
    from benchmark.span_readers import trace_reduction

    red = reduction(ctx)
    b = red.get("boundary")
    if not b:
        return None
    spans = trace_reduction(ctx)
    parts = {n: round(1e3 * v / b["count"], 4) for n, v in b["parts_s"].items()}
    print(f"serve.boundary: {b['count']} in the traced window, the longest {1e3 * b['max_s']:.3f} ms; ms a boundary: "
          f"{json.dumps(parts)}, self {1e3 * b['self_s'] / b['count']:.4f}", flush=True)
    if spans:
        after = sum(red["after_fence_s"].values())
        print(f"two clocks: the host's side of {red['host_gaps']} gaps {1e3 * red['host_gap_s']:.3f} ms (serve.boundary "
              f"{1e3 * b['total_s']:.3f}, accept and harvest {1e3 * after:.3f}, the rest a step's way to its first "
              f"dispatch) + fence tails {1e3 * red['fence_tail_s']:.3f} ms = "
              f"{1e3 * (red['host_gap_s'] + red['fence_tail_s']):.3f} ms; the trace's idle time "
              f"{1e3 * (spans['window_s'] - spans['busy_s']):.3f} ms", flush=True)
    return 1e3 * b["total_s"] / b["count"]


def host_gap_fed_ms_per_s(ctx):
    """The host gap of the record as it stood at the newest arrival, per
    second of that record: the window alone, where the generator stops at
    its end."""
    final = ctx.get("final", {})
    gap, fed_s = final.get("fed_host_gap_s"), final.get("fed_s")
    if gap is None or not fed_s:
        return None
    print(f"the record at the newest arrival: {fed_s:.3f} s of a {ctx.get('seconds', 0):g} s window; decode blocks "
          f"{final.get('fed_decode_blocks')} of {final.get('decode_blocks')}, steps {final.get('fed_decode_steps')} of "
          f"{final.get('decode_steps')}, tokens {final.get('fed_decode_tokens')} of {final.get('decode_tokens')}; "
          f"occupancy {final.get('fed_slot_occupancy_pct')} (whole {final.get('slot_occupancy_pct')}), yield "
          f"{final.get('fed_decode_yield_pct')} (whole {final.get('decode_yield_pct')}), decode tokens/s "
          f"{final.get('fed_decode_tokens_per_sec')} (whole {final.get('decode_tokens_per_sec')})", flush=True)
    return 1e3 * gap / fed_s


def main(argv) -> int:
    red = reduce_dir(argv[0])
    print(table(red), file=sys.stderr)
    print(json.dumps(red))
    return 0 if red else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
