"""Replica entry of the serving cells: ``python -m benchmark.entry_serve
--bench-config FILE --bench-state DIR [--bench-trace-s N] <serve.py args>``.

States the model, gives seeded weights, and calls the program's own
``workloads.serve.main``. Two small hooks on the engine's ``submit``: the
first measured request (id ``r...``; warm-up ids start with ``w``) resets
the engine's accumulators, so ``engine.stats()`` covers the window alone,
and in a traced run starts ``jax.profiler`` for a few seconds of it.
"""

from __future__ import annotations

import sys
from pathlib import Path

from .entry_common import bench_args, install, trace_in_background, write_report

TRACE_DELAY_S = 5.0


def main(argv=None) -> int:
    args, rest = bench_args(sys.argv[1:] if argv is None else argv)
    state = Path(args.bench_state)
    install(args)

    from pytorch_operator_tpu.serving.engine import ServingEngine
    from pytorch_operator_tpu.workloads import serve

    trace_dir = state / "trace" if args.bench_trace_s > 0 else None
    tracer = []

    engine_submit = ServingEngine.submit

    def submit(self, request):
        if not tracer and request.id.startswith("r"):
            self.reset_stats()
            tracer.append(
                trace_in_background(trace_dir, TRACE_DELAY_S, args.bench_trace_s)
                if trace_dir else None
            )
        return engine_submit(self, request)

    ServingEngine.submit = submit
    rc = serve.main(["--config", "bench", *rest])
    if tracer and tracer[0] is not None:
        tracer[0].join(timeout=120)
    write_report(state, trace_dir, ("prefill_chunk", "decode_block"))
    return rc


if __name__ == "__main__":
    sys.exit(main())
