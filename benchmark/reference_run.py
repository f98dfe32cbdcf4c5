"""The check's reference side, as a process of its own:
``python -m benchmark.reference_run CHECK_IN.json CHECK_OUT.json [--control]
[--bench DIR]``.

Reads what the harness wrote about a finished run (the configuration, the
seed, and for serving a sample of prompts with the tokens served), calls
the reference of the configuration's family (``family.py``: the file
``<DIR>/families/<bench.family>/reference.py``) and writes its numbers
beside how long it took and on what platform. ``--control`` adds the same
reading in the next precision down, which the limits were set against.

**The contract a family's ``reference.py`` meets.** Two functions over the
parsed ``check_in.json`` (``check``) and the ``--control`` flag, each
returning a dict of plain numbers that ``json`` can write:

- ``serve_check(check, control)``, for ``check["kind"] == "serve"``.
  ``check`` has ``config`` (the configuration file), ``seed``, ``pad_to``
  and ``width`` (the mix's longest prompt + answer, and longest answer) and
  ``requests``, each a ``prompt`` with the ``tokens`` served. Follow every
  request teacher-forced through the plain reference, weights from the seed
  in the precision the configuration states. Returns ``requests``,
  ``positions`` (served tokens compared), ``agree`` (of them, the
  reference's own first choice), ``gap_max`` and ``gap_mean`` (the gap by
  which a served token's logit lies below the reference's best) and, with
  ``control``, ``control_gap_max`` and ``control_gap_mean`` (the same gap of
  the token that the next precision down puts first).
- ``train_check(check, control)``, for ``"train"``. ``check`` has
  ``config``, ``seed``, ``steps``, ``batch``, ``seq_len`` and ``lr``; step
  ``s``'s tokens are ``entry_train.seeded_batch(seed, s, batch, seq_len,
  vocab_size)``. Returns ``losses`` (one a step), ``grad_norm`` (the first
  step's gradient norm as the optimizer gets it) and ``delta_norm`` (the
  parameters' change over the steps), both nested dicts with a number for
  each leaf of the program's parameter tree, per-layer leaves stacked; and,
  with ``control``, the same three under ``control``, computed in the next
  precision down.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from . import family

SERVE_KEYS = ("requests", "positions", "agree", "gap_max", "gap_mean")
SERVE_CONTROL_KEYS = ("control_gap_max", "control_gap_mean")
TRAIN_KEYS = ("losses", "grad_norm", "delta_norm")


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    control = "--control" in argv
    bench = Path(argv.pop(argv.index("--bench") + 1)) if "--bench" in argv else family.BENCH
    src, dst = [a for a in argv if not a.startswith("--")]
    check = json.loads(Path(src).read_text())
    import jax  # its compile cache is where JAX_COMPILATION_CACHE_DIR says; the harness sets it

    reference = family.of(check["config"], "reference", bench)
    t0 = time.time()
    out = (reference.serve_check if check["kind"] == "serve" else reference.train_check)(check, control)
    out["seconds"] = time.time() - t0
    out["platform"] = jax.devices()[0].platform
    Path(dst).write_text(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
