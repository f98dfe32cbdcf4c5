"""Tiny made-up serving cells of the ``jamba`` family (PR 46), added to a
temporary copy of the benchmark the way ``benchcells.py`` adds its own: new
files and entries only. The family itself is the benchmark's
(``benchmark/families/jamba``), at a small size with the real structure
(``data/cells/config.tiny-jamba.json``: six layers, attention of ONE
key/value head under four queries at layers 1 and 4, Mamba-1 with inner norms
elsewhere). The second cell is the same configuration served by a program
whose boundary takes 16 prompt tokens, so that every prompt longer than a
chunk is prefilled over several boundaries while the other rows decode
(``split_prefill_serve.py``): it must serve what the first serves. The third
starts every resumed part from zero state (``lost_state_split_serve.py``:
the row's scan state and tail did not live in its slot between the parts),
and is not correct.

As a program (``python -m tests.zz_benchmark.jambacells COPY CELL SECONDS``)
it drives one run of such a cell on the CPU and prints the result line.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from tests.zz_benchmark.benchcells import ROOT, make_copy

LIKE = "serve-jamba2-3b-longdoc"
# bfloat16 activations at width 64 against the float32 reference: readings in test_bench_runs_jamba.py.
LIMITS = {"served_logit_gap_max": 0.1}

# name -> ((configuration, traffic mix, the real cell whose metrics it reports, limits), entry module or None)
CELLS = {
    "tiny-jamba": (("tiny-jamba", "tiny-reasoning", LIKE, LIMITS), None),
    "tiny-jamba-split": (("tiny-jamba", "tiny-reasoning", LIKE, LIMITS), "tests.zz_benchmark.split_prefill_serve"),
    "tiny-jamba-lost-state": (("tiny-jamba", "tiny-reasoning", LIKE, LIMITS), "tests.zz_benchmark.lost_state_split_serve"),
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
