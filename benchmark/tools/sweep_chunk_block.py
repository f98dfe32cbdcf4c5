#!/usr/bin/env python3
"""The two sweeps a long-context serving configuration is sized from, in one
process on the chip. Not part of a run: ``benchmark/run.py`` never imports
this file.

    python benchmark/tools/sweep_chunk_block.py CONFIG.json [--chunks 128,256,512] [--blocks 512,1024,2048]

The configuration's family states the model to the program (``install.py``);
the engine's own programs (``serving.engine.programs``) are built over it at
the configuration's slots and ``max_decode_len``:

- **chunk**: a prompt of ``--prompt`` tokens prefilled into one slot, a chunk
  a call, timed from the first call to the last's result (the second of two
  passes: the first compiles): prompt tokens a second of prefill, by chunk
  size, at the block the tree has;
- **block**: with ``ops.cache_attention.BLOCK_MAX`` at each value, (a) a
  decode dispatch of 32 steps over every slot, the rows standing at depths
  spread over ``--depths`` (ms a step), and (b) the same prompt prefilled at
  the largest chunk (the chunk loop reads the slab in the same blocks).

Every line is JSON; the device is named on the first.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def show(**row) -> None:
    print(json.dumps(row), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("--chunks", default="128,256,512")
    ap.add_argument("--blocks", default="512,1024,2048")
    ap.add_argument("--prompt", type=int, default=16_384)
    ap.add_argument("--depths", default="2048,24576")
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import family
    from pytorch_operator_tpu.models.serving import preset
    from pytorch_operator_tpu.ops import cache_attention as ca
    from pytorch_operator_tpu.ops.sampling import make_sampler
    from pytorch_operator_tpu.serving.engine import programs

    config = json.loads(Path(a.config).read_text())
    family.of(config, "install").install(config)
    engine = config["bench"]["engine"]
    slots, L = engine["slots"], engine["max_decode_len"]
    cfg = preset("bench", decode=True, max_decode_len=L)
    model = cfg.serving_model()
    dev = jax.devices()[0]
    show(device=dev.device_kind, platform=dev.platform, config=config["name"], slots=slots, max_decode_len=L)
    params = model.init_params(jax.random.key(0))
    sample = make_sampler(0.0, 0, 1.0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (a.prompt,)).astype(np.int32)

    def prefill(progs, cache, counts, chunk):
        """Two passes of the prompt into slot 0; the second is timed."""
        for timed in (False, True):
            t0 = time.perf_counter()
            for start in range(0, a.prompt, chunk):
                hidden, cache, counts = progs.prefill_chunk(params, cache, counts, np.int32(0), prompt[None, start:start + chunk],
                                                            np.int32(start), np.int32(chunk))
            jax.block_until_ready(hidden)
            seconds = time.perf_counter() - t0
        return seconds, cache, counts

    for chunk in (int(c) for c in a.chunks.split(",")):
        progs = programs(model, slots=slots, chunk=chunk, block=engine["block"], sample=sample)
        seconds, cache, counts = prefill(progs, model.init_cache(slots, chunk), jax.tree.map(jnp.zeros_like, model.counts), chunk)
        show(sweep="chunk", chunk=chunk, block_positions=ca.block(L), prompt=a.prompt, seconds=seconds,
             prompt_tokens_per_s=a.prompt / seconds, ms_a_chunk=1e3 * seconds / (a.prompt // chunk))
        del cache, counts, progs

    lo, hi = (int(x) for x in a.depths.split(","))
    depths = np.linspace(lo, hi, slots).astype(np.int32)
    chunk = max(int(c) for c in a.chunks.split(","))
    for block in (int(b) for b in a.blocks.split(",")):
        ca.BLOCK_MAX = block
        progs = programs(model, slots=slots, chunk=chunk, block=engine["block"], sample=sample)
        cache, counts = model.init_cache(slots, chunk), jax.tree.map(jnp.zeros_like, model.counts)
        tok, rngkey, active = jnp.zeros((slots,), jnp.int32), jax.random.key(1), np.ones((slots,), bool)
        held = (np.zeros((slots,), bool),) if model.holds else ()
        steps = 32
        for timed in (False, True):
            t0 = time.perf_counter()
            toks, cache, counts, tok, pos, rngkey = progs.decode_block(
                params, cache, counts, tok, jnp.asarray(depths), active, rngkey, np.int32(steps), *held)
            jax.block_until_ready(toks)
            seconds = time.perf_counter() - t0
        read = int(ca.attended(depths + 1, L).sum())
        show(sweep="block", block_positions=ca.block(L), rows=slots, depths=[int(depths[0]), int(depths[-1])],
             live_positions=int(depths.sum()) + slots, attended_positions=read, ms_a_step=1e3 * seconds / steps)
        seconds, cache, counts = prefill(progs, cache, counts, chunk)
        show(sweep="block", block_positions=ca.block(L), chunk=chunk, prompt=a.prompt, prefill_seconds=seconds,
             prompt_tokens_per_s=a.prompt / seconds)
        del cache, counts, progs
    return 0


if __name__ == "__main__":
    sys.exit(main())
