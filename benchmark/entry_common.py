"""What the benchmark's two entry modules share. They run inside the
replica, the process that holds the chip: they have the configuration's
family state the cell's model to the program and give it seeded weights,
and afterwards report the allocator's peak and the reduced trace. Everything else is the program's own ``main``.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def bench_args(argv):
    """Split the benchmark's own arguments from the workload's."""
    p = argparse.ArgumentParser(add_help=False)
    p.add_argument("--bench-config", required=True)
    p.add_argument("--bench-state", required=True)
    p.add_argument("--bench-dir", default=str(Path(__file__).resolve().parent))
    p.add_argument("--bench-seconds", type=float, default=0.0)
    p.add_argument("--bench-trace-s", type=float, default=0.0)
    return p.parse_known_args(argv)


def install(args):
    """Read the configuration and hand it to its family's ``install.py``,
    which states the model to the program and gives it seeded weights.
    Returns the configuration and the family's ``weights`` module."""
    from . import family

    model = json.loads(Path(args.bench_config).read_text())
    family.of(model, "install", args.bench_dir).install(model)
    return model, family.of(model, "weights", args.bench_dir)


def trace_in_background(trace_dir, delay_s: float, seconds: float):
    """Trace ``seconds`` of the window from a thread of this process (only
    the process that holds the chip can trace it). Returns the thread."""
    import threading
    import time

    def run():
        import jax

        # Device operations and the runtime's own host spans; no Python-level
        # tracer (it slows the host and swells the trace) and no HLO dump.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        time.sleep(delay_s)
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        time.sleep(seconds)
        jax.profiler.stop_trace()

    thread = threading.Thread(target=run, daemon=True)
    thread.start()
    return thread


def write_report(state: Path, trace_dir, programs) -> None:
    """This replica's allocator statistics (the largest over its chips) and,
    in a traced run, its reduced trace, for the harness to read."""
    import jax

    from . import trace_reduce

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    most = lambda key: max((s.get(key) or 0) for s in stats)
    report = {key: most(key) for key in ("peak_bytes_in_use", "bytes_in_use", "peak_bytes_reserved")}
    if trace_dir:
        report["trace"] = trace_reduce.reduce_dir(str(trace_dir), programs)
    out = state / f"replica-{jax.process_index()}.json"
    out.write_text(json.dumps(report))
