"""Tiny made-up serving cells of the ``mimo_v2`` family (PR 28), added to a
temporary copy of the benchmark the way ``benchcells.py`` adds its own: new
files and entries only. The family itself is the benchmark's
(``benchmark/families/mimo_v2``), at a small size with the real structure
(``data/cells/config.tiny-mimo.json``); two further configurations tell the
reference to leave a mechanism out (``bench.reference_omits``).

As a program (``python -m tests.zz_benchmark.mimocells COPY CELL SECONDS``)
it drives one run of such a cell on the CPU and prints the result line.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from tests.zz_benchmark.benchcells import ROOT, make_copy

LIKE = "serve-mimo-v2.5-reasoning"
# bfloat16 activations at width 64 against the float32 reference read up to 0.03
# here over six seeds; a reference without the sink reads 1.1 or more, one
# without the selection bias 0.56 or more.
LIMITS = {"served_logit_gap_max": 0.25}

# name -> (configuration, traffic mix, the real cell whose metrics it reports, limits)
CELLS = {
    "tiny-mimo": ("tiny-mimo", "tiny-reasoning", LIKE, LIMITS),
    "tiny-mimo-no-sink": ("tiny-mimo-no-sink", "tiny-reasoning", LIKE, LIMITS),
    "tiny-mimo-no-e-bias": ("tiny-mimo-no-e-bias", "tiny-reasoning", LIKE, LIMITS),
}


def main(argv) -> int:
    sys.path.insert(0, str(ROOT))
    from benchmark import run

    copy, cell, seconds = Path(argv[0]), argv[1], float(argv[2])
    bench = make_copy(copy, {cell: CELLS[cell]})
    try:
        result = run.run_cell(cell, 2**31 + 11, seconds, False, bench=bench, platform="cpu")
    except run.BenchFailure as e:
        print(f"no result: {e}")
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
