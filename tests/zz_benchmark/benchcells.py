"""Tiny made-up cells for the benchmark's tests, added the way a later PR
adds a cell: new files beside the benchmark's and new entries in the
manifest, in a temporary copy, with no file of the benchmark edited. Two of
them are of a second, made-up family (``data/families/tiny-moe``: a layer of
experts, the trainer's ``MoEMLP``, standing on no other family), which comes as files too: the
rehearsal of a PR that brings a model of another shape.

As a program (``python -m tests.zz_benchmark.benchcells COPY CELL SECONDS
[ENTRY_MODULE]``) it drives one run of such a cell on the CPU — the whole
harness after its look for a chip — and prints the result line.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data" / "cells"
FAMILIES = Path(__file__).resolve().parent / "data" / "families"

MOE_LIMITS = {"loss_gap_step1": 0.01, "loss_gap_step2": 0.01, "loss_gap_step3": 0.01,
              "grad_norm_gap_worst_leaf": 0.02, "delta_norm_gap_worst_leaf": 0.1}

# name -> (configuration, traffic mix, the real cell whose metrics it reports, limits)
CELLS = {
    "tiny-chat": ("tiny-serve", "tiny-open", "serve-internlm2-chat", {"served_logit_gap_max": 0.05}),
    "tiny-long": ("tiny-serve", "tiny-closed", "serve-internlm2-longprompt", {"served_logit_gap_max": 0.05}),
    "tiny-pre": ("tiny-train", "tiny-pretrain", "train-mistral7b-1chip", {
        "loss_gap_step1": 0.01, "loss_gap_step2": 0.01, "loss_gap_step3": 0.01,
        "grad_norm_gap_worst_leaf": 0.004, "delta_norm_gap_worst_leaf": 0.1}),
    "tiny-moe": ("tiny-moe-train", "tiny-pretrain", "train-mistral7b-1chip", MOE_LIMITS),
    # The same program beside a reference whose router keeps one expert a token.
    "tiny-top1": ("tiny-moe-top1", "tiny-pretrain", "train-mistral7b-1chip", MOE_LIMITS),
}


def make_copy(copy: Path, cells=CELLS, suffix: str = "") -> Path:
    """Copy the benchmark and add ``cells`` (named ``<name><suffix>``) as
    files and entries only. Returns the copy's ``benchmark`` directory."""
    bench = copy / "benchmark"
    shutil.copytree(ROOT / "benchmark", bench, ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for family in FAMILIES.iterdir():
        shutil.copytree(family, bench / "families" / family.name, ignore=shutil.ignore_patterns("__pycache__"))
    for name, (config, mix, like, limits) in cells.items():
        name += suffix
        for kind, item, folder in (("config", config, "configs"), ("traffic", mix, "traffic")):
            shutil.copy(DATA / f"{kind}.{item}.json", bench / folder / f"{item}.json")
        if config not in [c["name"] for c in manifest["configs"]]:
            manifest["configs"].append({"name": config, "source": "made up for a test",
                                        "file": f"benchmark/configs/{config}.json", "reduced": [], "why": "test"})
        manifest["workloads"].append({"name": name, "config": config, "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        (bench / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": {k: {"limit": v} for k, v in limits.items()}}))
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items()), "a file of the benchmark was edited"
    return bench


def main(argv) -> int:
    sys.path.insert(0, str(ROOT))
    from benchmark import run

    copy, cell, seconds = Path(argv[0]), argv[1], float(argv[2])
    base = next(n for n in CELLS if cell.startswith(n))
    bench = make_copy(copy, {base: CELLS[base]}, suffix=cell[len(base):])
    try:
        result = run.run_cell(cell, 2**31 + 11, seconds, False, bench=bench, platform="cpu",
                              module=argv[3] if len(argv) > 3 else None)
    except run.BenchFailure as e:
        print(f"no result: {e}")
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
