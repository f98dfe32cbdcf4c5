"""The check's reference side, as a process of its own:
``python -m benchmark.reference_run CHECK_IN.json CHECK_OUT.json [--control]``.

Reads what the harness wrote about a finished run (the configuration, the
seed, and for serving a sample of prompts with the tokens served), runs
the plain reference and writes its numbers. ``--control`` adds the same
reading in the next precision down, which the limits were set against.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def serve_check(check: dict, control: bool) -> dict:
    import jax
    import numpy as np

    from . import reference as R
    from . import weights as W

    d = W.dims(check["config"])
    reqs = check["requests"]
    pad_to = int(check["pad_to"])
    tokens = np.zeros((len(reqs), pad_to), np.int32)
    first, count = [], []
    for i, r in enumerate(reqs):
        seq = list(r["prompt"]) + list(r["tokens"])
        if len(seq) > pad_to:
            raise SystemExit(f"request of {len(seq)} tokens exceeds the mix's check_pad_to {pad_to}")
        tokens[i, : len(seq)] = seq
        first.append(len(r["prompt"]) - 1)
        count.append(len(r["tokens"]))
    res = R.serve_gaps(
        d, jax.random.key(check["seed"]), jax.numpy.asarray(tokens), jax.numpy.asarray(first),
        jax.numpy.asarray(count), int(check["width"]), control_levels=7 if control else None,
    )
    valid = np.asarray(res["valid"])
    gaps = np.asarray(res["gap"])[valid].tolist()
    agree = int(np.asarray(res["agree"])[valid].sum())
    cgaps = np.asarray(res["control_gap"])[valid].tolist() if control else []
    out = {}
    out.update(requests=len(reqs), positions=len(gaps), agree=agree, gap_max=max(gaps),
               gap_mean=sum(gaps) / len(gaps))
    if control:
        out.update(control_gap_max=max(cgaps), control_gap_mean=sum(cgaps) / len(cgaps))
    return out


def train_check(check: dict, control: bool) -> dict:
    import jax

    from . import reference as R
    from . import weights as W
    from .entry_train import seeded_batch

    d = W.dims(check["config"])
    batches = [seeded_batch(check["seed"], s, check["batch"], check["seq_len"], d["V"])
               for s in range(check["steps"])]
    key = jax.random.key(check["seed"])
    out = R.train_steps(d, key, batches, lr=check["lr"])
    if control:
        out["control"] = R.train_steps(d, key, batches, lr=check["lr"], lower=True)
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    control = "--control" in argv
    src, dst = [a for a in argv if not a.startswith("--")]
    check = json.loads(Path(src).read_text())
    import jax  # its compile cache is where JAX_COMPILATION_CACHE_DIR says; the harness sets it

    t0 = time.time()
    out = (serve_check if check["kind"] == "serve" else train_check)(check, control)
    out["seconds"] = time.time() - t0
    out["platform"] = jax.devices()[0].platform
    Path(dst).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
