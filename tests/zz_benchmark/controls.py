"""The controls at the tiny size, as a program (``python -m
tests.zz_benchmark.controls serve|train SEED``): the reference in the next
precision down, read against the reference; prints the numbers as JSON."""

from __future__ import annotations

import json
import sys

from tests.zz_benchmark.benchcells import DATA


def serve(seed: int) -> dict:
    import jax
    import numpy as np

    from benchmark import reference as R
    from benchmark import weights as W

    d = W.dims(json.loads((DATA / "config.tiny-serve.json").read_text()))
    rng = np.random.default_rng(seed % 2**32)
    tokens = jax.numpy.asarray(rng.integers(0, d["V"], (2, 64)), jax.numpy.int32)
    out = R.serve_gaps(d, jax.random.key(seed % 2147483629), tokens, jax.numpy.asarray([20, 30]),
                       jax.numpy.asarray([16, 12]), 16, control_levels=7)
    valid = np.asarray(out["valid"])
    gaps = np.asarray(out["control_gap"])[valid]
    return {"positions": int(valid.sum()), "served_logit_gap_max": float(gaps.max()), "smallest": float(gaps.min())}


def train(seed: int) -> dict:
    import jax

    from benchmark import reference as R
    from benchmark import run
    from benchmark import weights as W
    from benchmark.entry_train import seeded_batch

    d = W.dims(json.loads((DATA / "config.tiny-train.json").read_text()))
    pseed = seed % 2147483629
    batches = [seeded_batch(pseed, s, 2, 64, d["V"]) for s in range(3)]
    key = jax.random.key(pseed)
    ref = R.train_steps(d, key, batches, lr=0.01)
    ref["control"] = R.train_steps(d, key, batches, lr=0.01, lower=True)
    return run.control_numbers("train", ref)


if __name__ == "__main__":
    print(json.dumps({"serve": serve, "train": train}[sys.argv[1]](int(sys.argv[2]))))
