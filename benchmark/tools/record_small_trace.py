#!/usr/bin/env python3
"""Record the small trace the tests of ``span_reduce.py`` read
(``tests/zz_benchmark/data/spans.xplane.pb``): a tiny serving engine on the
chip, a few requests through both of its programs under ``jax.profiler`` with
the benchmark's own options. Not part of a run.

    chiprun -- python benchmark/tools/record_small_trace.py chiprun_out/small_spans
"""

from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(out_dir: str) -> int:
    import flax.linen as nn
    import jax
    import numpy as np

    from pytorch_operator_tpu.models import llama as llama_lib
    from pytorch_operator_tpu.serving import Request, ServingEngine

    cfg = llama_lib.llama_tiny(decode=True, max_decode_len=64, kv_quantize="int8")
    params = nn.meta.unbox(
        llama_lib.Llama(dataclasses.replace(cfg, decode=False)).init(
            jax.random.key(0), np.zeros((1, 8), np.int32))["params"])
    eng = ServingEngine(cfg, params, slots=3, chunk=8, block=4)
    rng = np.random.default_rng(0)

    def submit(prefix, shapes):
        for i, (p, n) in enumerate(shapes):
            eng.submit(Request(id=f"{prefix}{i}", prompt=rng.integers(0, 256, (p,)).astype(np.int32),
                               max_new_tokens=n, submit_time=time.time()))

    submit("w", [(11, 6)])  # both programs compile outside the trace
    eng.run_until_drained()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        submit("r", [(5, 7), (13, 9), (8, 3), (21, 5)])
        while eng.busy:
            eng.step()
            time.sleep(0.002)  # a gap outside every engine span
            eng.host_lap("respond")
    finally:
        jax.profiler.stop_trace()
    print({k: v for k, v in eng.stats().items() if isinstance(v, (int, float))})
    print(jax.devices()[0].platform, jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
