"""Replica entry of the training cells: ``python -m benchmark.entry_train
--bench-config FILE --bench-state DIR --bench-seconds S [--bench-trace-s N]
<llama_train.py args>``.

States the model, gives seeded weights and seeded token batches, and calls
the program's own ``workloads.llama_train.main``. The trainer's
``throughput_loop`` is entered twice on the one compiled step and state:
first for the set-up steps (three warm-up steps, whose readings the check
compares with the reference, and a short timed probe for the step time), then for the window, whose step count
is ``--bench-seconds`` over the probe's step time. The rate reported is the
trainer's own, of the window's steps between two device fences.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time
from pathlib import Path

from .entry_common import bench_args, install, trace_in_background, write_report

CHECK_STEPS = 3
PROBE_STEPS = 3
TRACE_DELAY_S = 3.0


def seeded_batch(seed: int, step: int, batch: int, seq_len: int, vocab: int):
    """The token batch of one step: uniform tokens from (seed, step)."""
    import numpy as np

    return np.random.default_rng([seed, step]).integers(
        0, vocab, size=(batch, seq_len), dtype=np.int64
    ).astype(np.int32)


def leaf_paths(tree, prefix=()):
    for name, node in sorted(tree.items()):
        if isinstance(node, dict):
            yield from leaf_paths(node, prefix + (name,))
        else:
            yield "/".join(prefix + (name,)), node


def grad_squares(opt_state, params):
    """Sum of squared gradients per leaf, from Adafactor's statistics after
    its first step (decay 0: they hold the gradient's squares). A factored
    leaf keeps their mean over its largest axis, so the sum is
    ``sum(v_row) * leaf.size / v_row.size``; the others keep every square."""
    import jax
    import jax.numpy as jnp

    has_rows = lambda n: hasattr(n, "v_row")
    fs = next(s for s in jax.tree.leaves(opt_state, is_leaf=has_rows) if has_rows(s))
    rows, fulls = dict(leaf_paths(fs.v_row)), dict(leaf_paths(fs.v))
    out = {}
    for path, leaf in leaf_paths(params):
        if fulls[path].shape == leaf.shape:
            out[path] = jnp.sum(fulls[path].astype(jnp.float32))
        else:
            out[path] = jnp.sum(rows[path].astype(jnp.float32)) * (leaf.size // rows[path].size)
    return out


def delta_squares(params, make_start, key):
    """Per leaf, the squared norm of (params - the seeded start, which
    ``make_start(key, dtype)`` gives), one leaf to a program so that only
    one leaf's start is alive at a time."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in leaf_paths(params):
        def diff(p, key, path=path):  # the key an argument: one program for every seed
            start = make_start(key, p.dtype)
            for name in path.split("/"):
                start = start[name]
            return jnp.sum((p.astype(jnp.float32) - start.astype(jnp.float32)) ** 2)
        out[path] = jax.jit(diff)(leaf, key)
    return out


def main(argv=None) -> int:
    args, rest = bench_args(sys.argv[1:] if argv is None else argv)
    state_dir = Path(args.bench_state)
    model, W = install(args)
    seed = int(os.environ.get("TPUJOB_SEED", "0"))

    from pytorch_operator_tpu.workloads import llama_train, trainer

    llama_train.synthetic_bigram_batch = (
        lambda batch, seq_len, vocab, step: seeded_batch(seed, step, batch, seq_len, vocab)
    )
    trainer_loop = trainer.throughput_loop
    trace_dir = state_dir / "trace" if args.bench_trace_s > 0 else None

    def windowed_loop(train_step, state, batches, *, steps, warmup, device_get,
                      on_first_step=None, profile_dir=None, start_step=0, **kw):
        import flax.linen as nn
        import jax

        seen = {"calls": 0, "losses": []}

        def checked_step(state, tokens):
            state, loss = train_step(state, tokens)
            seen["calls"] += 1
            params = nn.meta.unbox(state["params"])
            if seen["calls"] <= CHECK_STEPS:
                seen["losses"].append(loss)
            if seen["calls"] == 1:
                seen["grad_sq"] = jax.jit(grad_squares)(state["opt_state"], params)
            if seen["calls"] == CHECK_STEPS:
                seen["delta_sq"] = delta_squares(
                    params, lambda key, dtype: W.make_params(W.dims(model), key, dtype), jax.random.key(seed))
            return state, loss

        state, _, rate, end = trainer_loop(
            checked_step, state, batches, steps=PROBE_STEPS, warmup=max(warmup, CHECK_STEPS),
            device_get=device_get, on_first_step=on_first_step, start_step=start_step, **kw,
        )
        window_steps = max(PROBE_STEPS, math.ceil(args.bench_seconds * rate))
        if jax.process_index() == 0:
            norms = lambda sq: {k: math.sqrt(max(float(v), 0.0)) for k, v in sq.items()}
            (state_dir / "check_program.json").write_text(json.dumps({
                "losses": [float(device_get(l)) for l in seen["losses"]],
                "grad_norm": norms(seen["grad_sq"]),
                "delta_norm": norms(seen["delta_sq"]),
                "window_steps": window_steps,
                # The window opens after the second entry's one warm-up step.
                "window_start": time.time() + 1.0 / rate,
            }))
        tracer = (
            trace_in_background(trace_dir, TRACE_DELAY_S, args.bench_trace_s) if trace_dir else None
        )
        out = trainer_loop(
            train_step, state, batches, steps=window_steps, warmup=1, device_get=device_get, start_step=end, **kw,
        )
        if tracer is not None:
            tracer.join(timeout=120)
        # Here, not after main(): a gang's main() ends in the rendezvous'
        # own exit and does not return.
        write_report(state_dir, trace_dir, ("train_step",))
        return out

    trainer.throughput_loop = windowed_loop
    return llama_train.main(["--config", "bench", *rest])


if __name__ == "__main__":
    sys.exit(main())
