"""The controls at the tiny size, as a program (``python -m
tests.zz_benchmark.controls serve|train|contract SEED``): the reference in
the next precision down, read against the reference, and the contract of a
family's two drivers (``benchmark/reference_run.py``); prints the numbers as
JSON. The reference is reached through the configuration's family, as the
harness reaches it."""

from __future__ import annotations

import json
import sys
import tempfile
from pathlib import Path

from tests.zz_benchmark.benchcells import DATA


def config(name: str) -> dict:
    return json.loads((DATA / f"config.{name}.json").read_text())


def serve(seed: int) -> dict:
    import jax
    import numpy as np

    from benchmark import family

    model = config("tiny-serve")
    d = family.of(model, "weights").dims(model)
    rng = np.random.default_rng(seed % 2**32)
    tokens = jax.numpy.asarray(rng.integers(0, d["V"], (2, 64)), jax.numpy.int32)
    out = family.of(model, "reference").serve_gaps(
        d, jax.random.key(seed % 2147483629), tokens, jax.numpy.asarray([20, 30]),
        jax.numpy.asarray([16, 12]), 16, control_levels=7)
    valid = np.asarray(out["valid"])
    gaps = np.asarray(out["control_gap"])[valid]
    return {"positions": int(valid.sum()), "served_logit_gap_max": float(gaps.max()), "smallest": float(gaps.min())}


def train_check_in(model: dict, seed: int) -> dict:
    return {"kind": "train", "config": model, "seed": seed % 2147483629, "steps": 3, "batch": 2, "seq_len": 64,
            "lr": 0.01}


def train(seed: int) -> dict:
    from benchmark import family, run

    model = config("tiny-train")
    ref = family.of(model, "reference").train_check(train_check_in(model, seed), True)
    return run.control_numbers("train", ref)


def contract(seed: int) -> dict:
    """Both kinds of ``check_in.json`` at the tiny size through
    ``reference_run.main`` with ``--control``; the keys that came back."""
    import random

    from benchmark import reference_run, run

    rng = random.Random(seed)
    model = config("tiny-serve")
    requests = [{"prompt": [rng.randrange(model["vocab_size"]) for _ in range(n)],
                 "tokens": [rng.randrange(model["vocab_size"]) for _ in range(m)]} for n, m in ((21, 8), (9, 5))]
    checks = {"serve": {"kind": "serve", "config": model, "seed": seed % 2147483629, "pad_to": 32, "width": 8,
                        "requests": requests},
              "train": train_check_in(config("tiny-train"), seed)}
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        for kind, check in checks.items():
            src, dst = Path(tmp) / f"{kind}_in.json", Path(tmp) / f"{kind}_out.json"
            src.write_text(json.dumps(check))
            assert reference_run.main([str(src), str(dst), "--control"]) == 0
            got = json.loads(dst.read_text())
            out[kind] = sorted(got)
            if kind == "serve":
                out["positions"] = got["positions"]
            else:
                out["control"] = sorted(got["control"])
                out["leaves"] = [sorted(run.flatten(got[k])) for k in ("grad_norm", "delta_norm")]
    return out


if __name__ == "__main__":
    print(json.dumps({"serve": serve, "train": train, "contract": contract}[sys.argv[1]](int(sys.argv[2]))))
