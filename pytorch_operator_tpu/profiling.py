"""Profile-report tool: wall-time breakdown from a jax.profiler trace.

SURVEY.md §5 "Tracing / profiling": the reference has none of its own
(training-side profiling is user-container business); the rebuild's
workloads write ``jax.profiler`` traces via ``--profile-dir``. This
module closes the loop WITHOUT tensorboard: it reads the trace's
``*.xplane.pb`` with ``jax.profiler.ProfileData`` (nothing but JAX) and
prints where device time goes — per-step busy/idle split, op-category
totals, and the top individual ops.

Usage::

    python -m pytorch_operator_tpu.workloads.llama_train ... --profile-dir /tmp/prof
    python -m pytorch_operator_tpu.workloads.serve ... --profile-dir /tmp/prof
    python -m pytorch_operator_tpu.profiling /tmp/prof [--top 12] [--json]

The trace is planes (one per device, one for the host's threads) →
lines (Steps / XLA Ops / a thread) → timed events named by the HLO
operation, the runtime call or the program's own span (``obs.span``
mirrors every span into the trace: ``--device host:CPU`` lists them).
An unreadable trace is a clear error, never a crash: this is a
diagnostics path.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Optional

_NS = 1e-9


def find_xplane(profile_dir) -> Path:
    """Newest ``*.xplane.pb`` under a ``--profile-dir`` tree."""
    paths = sorted(
        Path(profile_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime
    )
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {profile_dir}")
    return paths[-1]


def _display(name: str) -> str:
    """A device event is named by its HLO text, ``%fusion.12 = bf16[...]
    fusion(...)``: the operation's own name is what stands before ``=``."""
    return name.split(" = ")[0].lstrip("%$") or name


def _category(display_name: str) -> str:
    """HLO op display names carry a ``kind.N`` suffix — strip the serial
    to get the category (fusion, copy, all-reduce, custom-call, ...)."""
    return re.sub(r"[.\-]?\d+$", "", display_name) or display_name


def _aggregate_self_times(events, by_cat, by_op) -> float:
    """Charge each event its SELF time (duration minus enclosed children)
    into the aggregates; returns the line's total busy seconds.

    Events nest within a line (a layer-scan ``while`` contains its body
    ops; a python frame contains its callees) — self-time keeps the
    total equal to true busy time instead of double-counting every
    nesting level. An interval stack over offset-sorted events recovers
    the tree.
    """
    busy = 0.0
    stack: list = []  # [end_ns, name, start_ns, child_ns]

    def pop(ev_start_ns) -> None:
        nonlocal busy
        while stack and (ev_start_ns is None or stack[-1][0] <= ev_start_ns):
            end, name, start, child = stack.pop()
            dur = end - start
            if stack:
                stack[-1][3] += dur
            dt = (dur - child) * _NS
            busy += dt
            name = _display(name)
            by_cat[_category(name)] += dt
            by_op[name] += dt

    # Outer intervals must be pushed before children that share their
    # start timestamp — longest-first at ties keeps the nesting upright
    # (child-first would charge the child a negative self time).
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        pop(start)
        stack.append([start + dur, name, start, 0])
    pop(None)
    return busy


def device_report(profile_dir, device_substr: str = "TPU") -> Optional[dict]:
    """Aggregate the device plane into a wall breakdown dict.

    Returns None when the trace has no matching device plane (e.g. a
    CPU-only run asked for TPU).
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(profile_dir)))
    lines: dict = {}
    report: dict = {}
    for plane in data.planes:
        if device_substr not in plane.name:
            continue
        lines = {
            line.name: [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            for line in plane.lines
        }
        if lines:
            report["device"] = plane.name
            break
    if not lines:
        return None

    steps = lines.get("Steps")
    if steps:
        durs = [dur * _NS for _, _, dur in steps]
        report["steps"] = len(durs)
        report["mean_step_s"] = sum(durs) / len(durs)
        report["span_s"] = sum(durs)

    # Per-op accounting: the device's "XLA Ops" line when present (TPU
    # traces), else every thread line (host/CPU traces, where the events
    # are python/runtime frames — still a useful where-does-time-go).
    if "XLA Ops" in lines:
        op_lines = [lines["XLA Ops"]]
    else:
        op_lines = [
            events for name, events in lines.items()
            if events and name not in ("Steps", "XLA Modules")
        ]
    if any(op_lines):
        by_cat: dict = defaultdict(float)
        by_op: dict = defaultdict(float)
        busy = 0.0
        for events in op_lines:
            busy += _aggregate_self_times(events, by_cat, by_op)
        if busy <= 0:
            # All-zero-duration events (truncated capture, instant
            # markers): no meaningful breakdown — report what exists
            # rather than dividing by zero below.
            return report
        report["busy_s"] = busy
        # Busy-vs-span is a utilization figure only for the single device
        # op line; summing N concurrent host threads against wall time
        # would read >100% and mean nothing.
        if len(op_lines) == 1 and report.get("span_s", 0) > 0:
            report["busy_frac_of_steps"] = busy / report["span_s"]
        n = report.get("steps") or 1
        report["categories"] = sorted(
            (
                {"category": c, "s_per_step": t / n, "pct_of_busy": 100 * t / busy}
                for c, t in by_cat.items()
            ),
            key=lambda r: -r["s_per_step"],
        )
        report["top_ops"] = sorted(
            (
                {"op": o, "s_per_step": t / n, "pct_of_busy": 100 * t / busy}
                for o, t in by_op.items()
            ),
            key=lambda r: -r["s_per_step"],
        )
    return report


def format_report(report: dict, top: int = 12) -> str:
    out = [f"device: {report['device']}"]
    if "steps" in report:
        out.append(
            f"steps: {report['steps']}  mean {report['mean_step_s']*1e3:.2f} ms/step"
        )
    if "busy_s" in report:
        n = report.get("steps") or 1
        line = f"device busy: {report['busy_s']/n*1e3:.2f} ms/step"
        if "busy_frac_of_steps" in report:
            line += f" ({100*report['busy_frac_of_steps']:.1f}% of step span)"
        out.append(line)
    if report.get("categories"):
        out.append("\nby op category (ms/step, % of busy):")
        for r in report["categories"][:top]:
            out.append(
                f"  {r['s_per_step']*1e3:8.2f}  {r['pct_of_busy']:5.1f}%  "
                f"{r['category']}"
            )
    if report.get("top_ops"):
        out.append(f"\ntop {top} ops (ms/step, % of busy):")
        for r in report["top_ops"][:top]:
            out.append(
                f"  {r['s_per_step']*1e3:8.2f}  {r['pct_of_busy']:5.1f}%  {r['op']}"
            )
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("profile_dir", help="the --profile-dir a workload wrote")
    p.add_argument("--device", default="TPU", help="device plane substring")
    p.add_argument("--top", type=int, default=12)
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)
    try:
        report = device_report(args.profile_dir, args.device)
    except (RuntimeError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    except Exception as e:  # corrupt/truncated trace (the reader's own error)
        print(f"error: unreadable trace: {e!r}", file=sys.stderr)
        return 1
    if report is None:
        print(
            f"error: no '{args.device}' device plane in the trace "
            "(try --device CPU)",
            file=sys.stderr,
        )
        return 1
    if args.as_json:
        # Trim the unbounded op table for machine consumers too.
        report["top_ops"] = report.get("top_ops", [])[: args.top]
        print(json.dumps(report, indent=2))
    else:
        print(format_report(report, args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
