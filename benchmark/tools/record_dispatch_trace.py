#!/usr/bin/env python3
"""Record the small trace the tests of ``dispatch_reduce.py`` read
(``tests/zz_benchmark/data/dispatch.xplane.pb``): the program's own serve loop
over a tiny engine on the chip, a closed loop of callers feeding its spool, and
``jax.profiler`` started from a thread in the middle of it with the benchmark's
own options, as a traced run starts it, so the trace opens on a dispatch that
is already in flight and closes on one whose fence has not returned. Prints the
engine's final record, for the tests to hold the reduction against. Not part of
a run.

    chiprun -- python benchmark/tools/record_dispatch_trace.py chiprun_out/dispatch_trace
"""

from __future__ import annotations

import json
import sys
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

CALLERS, REQUESTS = 6, 400
TRACE_FROM_S, TRACE_S = 0.5, 0.06


def main(out_dir: str) -> int:
    import jax
    import numpy as np

    from benchmark.entry_common import trace_in_background
    from pytorch_operator_tpu.serving import Spool
    from pytorch_operator_tpu.serving.engine import ServingEngine
    from pytorch_operator_tpu.workloads import serve

    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    spool = Spool(out / "spool")
    rng = np.random.default_rng(0)
    shapes = [(int(rng.integers(3, 30)), int(rng.integers(2, 24))) for _ in range(REQUESTS)]

    def callers():
        """The warm-up request alone, then ``CALLERS`` requests in flight until the supply ends."""
        spool.wait_response(spool.submit(prompt_len=11, max_new_tokens=6, request_id="w0"), timeout=600)
        waiting, supply = set(), iter(enumerate(shapes))
        while True:
            waiting = {rid for rid in waiting if not spool.has_response(rid)}
            while len(waiting) < CALLERS:
                nxt = next(supply, None)
                if nxt is None:
                    return
                i, (p, n) = nxt
                waiting.add(spool.submit(prompt_len=p, max_new_tokens=n, request_id=f"r{i:03d}"))
            time.sleep(0.001)

    # As ``benchmark/entry_serve.py``: the first measured request clears the record and starts the tracer.
    engine_submit, tracer = ServingEngine.submit, []

    def submit(self, request):
        if not tracer and request.id.startswith("r"):
            self.reset_stats()
            tracer.append(trace_in_background(out / "trace", TRACE_FROM_S, TRACE_S))
        return engine_submit(self, request)

    ServingEngine.submit = submit
    threading.Thread(target=callers, daemon=True).start()
    stats = serve.run(config="tiny", spool_dir=str(out / "spool"), slots=4, chunk=8, block=8, max_decode_len=64,
                      max_requests=REQUESTS + 1, idle_timeout=30, poll_interval=0.002, log=lambda *_: None)
    tracer[0].join(timeout=60)
    final = {k: v for k, v in stats.items() if isinstance(v, (int, float, str))}
    (out / "final.json").write_text(json.dumps(final))
    print(json.dumps(final))
    print(jax.devices()[0].platform, jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
