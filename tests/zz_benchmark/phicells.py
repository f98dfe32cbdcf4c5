"""Tiny made-up serving cells of the ``phi4_flash`` family (PR 36), added to
a temporary copy of the benchmark the way ``benchcells.py`` adds its own: new
files and entries only. The family itself is the benchmark's
(``benchmark/families/phi4_flash``), at a small size with the real structure
(``data/cells/config.tiny-phi4-flash.json``: two Mamba-1 layers and two
windows of 8, the Mamba layer that hands its memory on, the full layer, a
gated memory unit and a cross layer). The second cell is the same
configuration served by a program whose prefill never writes the slab, so
the full layer and the cross layers attend what the slot's last occupant
left there (``stale_slab_serve.py``); the third by one that never starts a
slot's row from zero scan state (``stale_scan_serve.py``), under prompts of 3
to 8 tokens: a longer prompt lets the stale state decay before the first
served token.

As a program (``python -m tests.zz_benchmark.phicells COPY CELL SECONDS``)
it drives one run of such a cell on the CPU and prints the result line.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

from tests.zz_benchmark.benchcells import ROOT, make_copy

LIKE = "serve-phi4-mini-flash-reasoning"
# bfloat16 activations at width 64 against the float32 reference: readings in test_bench_runs_phi4_flash.py.
LIMITS = {"served_logit_gap_max": 0.2}

# name -> ((configuration, traffic mix, the real cell whose metrics it reports, limits), entry module or None)
CELLS = {
    "tiny-phi4-flash": (("tiny-phi4-flash", "tiny-reasoning", LIKE, LIMITS), None),
    "tiny-phi4-flash-short": (("tiny-phi4-flash", "tiny-short-prompts", LIKE, LIMITS), None),
    "tiny-phi4-flash-stale-slab": (("tiny-phi4-flash", "tiny-reasoning", LIKE, LIMITS), "tests.zz_benchmark.stale_slab_serve"),
    "tiny-phi4-flash-stale-scan": (("tiny-phi4-flash", "tiny-short-prompts", LIKE, LIMITS), "tests.zz_benchmark.stale_scan_serve"),
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
