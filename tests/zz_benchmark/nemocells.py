"""Tiny made-up serving cells of the ``nemotron_h`` family (PR 33), added to
a temporary copy of the benchmark the way ``benchcells.py`` adds its own: new
files and entries only. The family itself is the benchmark's
(``benchmark/families/nemotron_h``), at a small size with the real structure
(``data/cells/config.tiny-nemotron.json``: ``MEM*EME``). The second cell is
the same configuration served by a program that never starts a slot's row
from zero state (``stale_state_serve.py``).

As a program (``python -m tests.zz_benchmark.nemocells COPY CELL SECONDS``)
it drives one run of such a cell on the CPU and prints the result line.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from tests.zz_benchmark.benchcells import ROOT, make_copy

LIKE = "serve-nemotron3-nano-reasoning"
# bfloat16 activations at width 64 against the float32 reference: where no
# selection falls the other way the served token's gap reads 0 to 0.1 (18
# runs over windows of 1.5 to 4 s, each with its own sample of 4 requests),
# a selection that does reads 0.3 to 0.7, so the tiny configuration's
# check.edge is 2^-5 (half of ~125 positions compared); a row that starts
# from its last occupant's state reads 0.35 to 1.2 under prompts of 3 to 8
# tokens (``tiny-short-prompts``: a longer prompt lets the stale state decay
# before the first served token).
LIMITS = {"served_logit_gap_max": 0.2}

# name -> ((configuration, traffic mix, the real cell whose metrics it reports, limits), entry module or None)
CELLS = {
    "tiny-nemotron": (("tiny-nemotron", "tiny-short-prompts", LIKE, LIMITS), None),
    "tiny-nemotron-stale-state": (("tiny-nemotron", "tiny-short-prompts", LIKE, LIMITS), "tests.zz_benchmark.stale_state_serve"),
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
