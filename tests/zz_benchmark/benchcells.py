"""Tiny made-up cells for the benchmark's tests, added the way a later PR
adds a cell: new files beside the benchmark's and new entries in the
manifest, in a temporary copy, with no file of the benchmark edited. Two of
them are of a second, made-up family (``data/families/tiny-moe``: a layer of
experts, the trainer's ``MoEMLP``, standing on no other family), which comes as files too: the
rehearsal of a PR that brings a model of another shape.

The copy is also what the rule files run on a second time (``rules.py``;
``test_bench_manifest.py``, ``test_bench_supply.py``, ``test_bench_traffic.py``):
``appended_copy`` adds, beside ``CELLS``, what the next PRs will append and no
cell of the benchmark is yet (``MORE_CELLS``): a second closed cell on a mix
another cell uses, one on a closed mix of its own (the two ways a sixth closed
cell can arrive), and a cell deeper than any cache of today (a configuration
whose ``max_decode_len`` is 8192 under a mix whose longest prompt + answer is
6,000). There every data file is as the rules want a file of the benchmark: a
configuration states its ``deployment``, a closed mix holds the ``supply``
rule. Nothing runs those: the rules read files. A rule that cannot survive an
appended cell is then red in the PR that writes it, not in the one that
appends the cell and may not mend it.

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

SERVE_LIMITS = {"served_logit_gap_max": 0.05}
MOE_LIMITS = {"loss_gap_step1": 0.01, "loss_gap_step2": 0.01, "loss_gap_step3": 0.01,
              "grad_norm_gap_worst_leaf": 0.02, "delta_norm_gap_worst_leaf": 0.1}

# name -> (configuration, traffic mix, the real cell whose metrics it reports, limits)
CELLS = {
    "tiny-chat": ("tiny-serve", "tiny-open", "serve-internlm2-chat", SERVE_LIMITS),
    "tiny-long": ("tiny-serve", "tiny-closed", "serve-internlm2-longprompt", SERVE_LIMITS),
    "tiny-pre": ("tiny-train", "tiny-pretrain", "train-mistral7b-1chip", {
        "loss_gap_step1": 0.01, "loss_gap_step2": 0.01, "loss_gap_step3": 0.01,
        "grad_norm_gap_worst_leaf": 0.004, "delta_norm_gap_worst_leaf": 0.1}),
    "tiny-moe": ("tiny-moe-train", "tiny-pretrain", "train-mistral7b-1chip", MOE_LIMITS),
    # The same program beside a reference whose router keeps one expert a token.
    "tiny-top1": ("tiny-moe-top1", "tiny-pretrain", "train-mistral7b-1chip", MOE_LIMITS),
}


# Only in the rule files' copy (``appended_copy``); their files are made there, not kept under ``data/``.
MORE_CELLS = {
    "tiny-long-2": ("tiny-serve", "tiny-closed", "serve-internlm2-longprompt", SERVE_LIMITS),
    "tiny-long-b": ("tiny-serve", "tiny-closed-b", "serve-internlm2-longprompt", SERVE_LIMITS),
    "tiny-deep": ("tiny-serve-deep", "tiny-deep-closed", "serve-internlm2-longprompt", SERVE_LIMITS),
}


def make_copy(copy: Path, cells=CELLS, suffix: str = "", files: dict | None = None) -> Path:
    """Copy the benchmark and add ``cells`` (named ``<name><suffix>``) as
    files and entries only. A cell's configuration and traffic files are
    those under ``data/cells``, or what ``files`` holds under the same name
    (``config.<name>``, ``traffic.<name>``). Returns the copy's ``benchmark``
    directory."""
    bench = copy / "benchmark"
    shutil.copytree(ROOT / "benchmark", bench, ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    for family in FAMILIES.iterdir():
        shutil.copytree(family, bench / "families" / family.name, ignore=shutil.ignore_patterns("__pycache__"))
    for name, (config, mix, like, limits) in cells.items():
        name += suffix
        for kind, item, folder in (("config", config, "configs"), ("traffic", mix, "traffic")):
            made = (files or {}).get(f"{kind}.{item}")
            text = (DATA / f"{kind}.{item}.json").read_text() if made is None else json.dumps(made)
            (bench / folder / f"{item}.json").write_text(text)
        if config not in [c["name"] for c in manifest["configs"]]:
            stated = json.loads((bench / "configs" / f"{config}.json").read_text())
            manifest["configs"].append({"name": config, "source": stated["source"], "file": f"benchmark/configs/{config}.json",
                                        "reduced": sorted(stated["reduced"]), "why": "test"})
        manifest["workloads"].append({"name": name, "config": config, "traffic": mix, "chips": 1, "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(name)
        (bench / "limits" / f"{name}.json").write_text(
            json.dumps({"limits": {k: {"limit": v} for k, v in limits.items()}}))
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    assert all(p.read_bytes() == b for p, b in before.items()), "a file of the benchmark was edited"
    return bench


def holding_the_supply_rule(mix: dict, sent: int) -> dict:
    """A made-up closed mix as a file of the benchmark has to be: twice what a window sends, in whole tables
    (``benchmark/traffic.py:schedule``). ``sent`` is made up too; no chip ran these."""
    rows = len(mix["lengths"])
    supply = 2 * rows * -(-sent // rows)
    return {**mix, "supply": supply,
            "sent_a_window": {"requests": sent, "seed": 2**31 + 11,
                              "run": "no chip run, PR 45: made up, where a file of the benchmark names its builder's"},
            "supply_note": f"{supply} = 2 x {sent} requests a window, rounded up to whole tables of {rows} rows"}


def appended_files() -> dict:
    """Every configuration and traffic file of the rule files' copy, by ``make_copy``'s names: those under
    ``data/cells`` that ``CELLS`` and ``MORE_CELLS`` use, completed to what the rules ask of a file of the
    benchmark (the files the run tests use stay as they are), and the three that only ``MORE_CELLS`` has."""
    data = lambda name: json.loads((DATA / f"{name}.json").read_text())
    serve, closed = data("config.tiny-serve"), data("traffic.tiny-closed")
    deep_args = list(serve["bench"]["args"])
    deep_args[deep_args.index("--max-decode-len") + 1] = "8192"
    deep_bench = {**serve["bench"], "engine": {**serve["bench"]["engine"], "max_decode_len": 8192}, "args": deep_args}
    files = {"config.tiny-serve-deep": {**serve, "name": "tiny-serve-deep", "bench": deep_bench},
             "traffic.tiny-closed-b": {**closed, "name": "tiny-closed-b"},
             # The longest prompt + answer is 6,000: past every cache of today, inside this configuration's.
             "traffic.tiny-deep-closed": holding_the_supply_rule(
                 {**closed, "name": "tiny-deep-closed", "check_pad_to": 6144,
                  "lengths": [[5000, 1000], [3000, 500], [4096, 904]]}, sent=3)}
    for config, mix, _like, _limits in {**CELLS, **MORE_CELLS}.values():
        for name in (f"config.{config}", f"traffic.{mix}"):
            if name not in files:
                files[name] = data(name)
    for name, made in files.items():
        if name.startswith("config."):
            made.setdefault("deployment", "made up for a test")
        elif made.get("loop") == "closed" and "sent_a_window" not in made:
            files[name] = holding_the_supply_rule(made, sent=100)  # of tiny-closed, a 3 s window on this sandbox's CPU
    return files


def appended_copy(copy: Path) -> Path:
    """The copy the rule files run on: the benchmark with ``CELLS`` and ``MORE_CELLS`` appended. Returns the
    copy's root, the directory that holds ``BENCHMARK.json`` and ``benchmark/``."""
    make_copy(copy, {**CELLS, **MORE_CELLS}, files=appended_files())
    # The manifest's other path: this directory itself, which a later PR leaves where it is.
    (copy / "tests").mkdir()
    (copy / "tests" / "zz_benchmark").symlink_to(Path(__file__).resolve().parent, target_is_directory=True)
    return copy


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
