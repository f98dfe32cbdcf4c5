"""Tiny made-up serving cells of the ``exaone_moe`` family (PR 41), added to a
temporary copy of the benchmark the way ``benchcells.py`` adds its own: new
files and entries only. The family itself is the benchmark's
(``benchmark/families/exaone_moe``), at a small size with the real structure
(``data/cells/config.tiny-exaone.json``: window + dense, window, window, full,
window with experts and a shared one, and the multi-token-prediction block
that drafts); two further cells run the same configuration through entry
modules whose verifying step is broken (``accept_all_serve.py``,
``stale_keys_serve.py``).

As a program (``python -m tests.zz_benchmark.exaonecells COPY CELL SECONDS``)
it drives one run of such a cell on the CPU and prints the result line.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from tests.zz_benchmark.benchcells import ROOT, make_copy

LIKE = "serve-k-exaone-reasoning"
# bfloat16 activations at width 64 against the float32 reference: readings in test_bench_runs_exaone.py.
LIMITS = {"served_logit_gap_max": 0.05}

# name -> ((configuration, traffic mix, the real cell whose metrics it reports, limits), entry module or None)
CELLS = {
    "tiny-exaone": (("tiny-exaone", "tiny-reasoning", LIKE, LIMITS), None),
    "tiny-exaone-accept-all": (("tiny-exaone", "tiny-reasoning", LIKE, LIMITS), "tests.zz_benchmark.accept_all_serve"),
    "tiny-exaone-stale-keys": (("tiny-exaone", "tiny-reasoning", LIKE, LIMITS), "tests.zz_benchmark.stale_keys_serve"),
}


def main(argv) -> int:
    sys.path.insert(0, str(ROOT))
    from benchmark import run

    copy, cell, seconds = Path(argv[0]), argv[1], float(argv[2])
    files, module = CELLS[cell]
    bench = make_copy(copy, {cell: files})
    try:
        result = run.run_cell(cell, 2**31 + 11, seconds, False, bench=bench, platform="cpu", module=module)
    except run.BenchFailure as e:
        print(f"no result: {e}")
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
