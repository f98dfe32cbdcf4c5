"""What the rule files read of a benchmark tree, and the means to run every
rule twice: on the real tree, and on a copy with cells appended.

A rule is a test of ``test_bench_manifest.py``, ``test_bench_supply.py`` or
``test_bench_traffic.py`` that takes a ``tree`` (``tree=REAL``, so the real
tree's cases keep the names they always had). ``over`` parametrises it over
what the tree holds (its cells, its closed mixes ...); ``copy_cases`` gives
each module one more test, which runs every rule of the module on
``benchcells.appended_copy``'s copy: the benchmark as the next PRs will leave
it, cells added as files and entries alone. A test that pins what an appended
cell changes (a count of cells, a list's end, a size that every configuration
of today happens to state) is red there in the PR that writes it. A rule is
not left out of the copy because it fails there; ``NOT_ON_THE_COPY`` names
the tests that read one file's own record and have nothing to read in a
made-up cell.
"""

from __future__ import annotations

import inspect
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from tests.zz_benchmark.benchcells import CELLS, MORE_CELLS, ROOT, appended_copy, appended_files


class Tree:
    """One benchmark tree: ``root`` holds ``BENCHMARK.json`` and ``benchmark/``. ``manifest`` stands in for the
    file's where a test asks what a rule says of the same files under other entries."""

    def __init__(self, root: Path, manifest: dict | None = None):
        self.root, self.bench = Path(root), Path(root) / "benchmark"
        self.manifest = manifest or json.loads((self.root / "BENCHMARK.json").read_text())
        self.cells = [w["name"] for w in self.manifest["workloads"]]
        self.e2e = {m["name"]: m for m in self.manifest["end_to_end"]}
        files = sorted((self.bench / "traffic").glob("*.json"), key=lambda p: p.stem)
        self.mixes = {p.stem: json.loads(p.read_text()) for p in files}
        self.serve_mixes = [name for name, mix in self.mixes.items() if "loop" in mix]
        self.closed = [name for name, mix in self.mixes.items() if mix.get("loop") == "closed"]
        self.closed_cells = [w["name"] for w in self.manifest["workloads"] if w["traffic"] in self.closed]

    def config_of(self, cell: dict) -> dict:
        entry = next(c for c in self.manifest["configs"] if c["name"] == cell["config"])
        return json.loads((self.root / entry["file"]).read_text())


REAL = Tree(ROOT)

# What the copy will hold, known before it is made: pytest wants a test's cases while it collects.
# ``test_the_copy_holds_what_its_cases_were_made_from`` holds the copy to it.
_MIXES = {name.split(".", 1)[1]: made for name, made in appended_files().items() if name.startswith("traffic.")}
PLANNED = SimpleNamespace(
    cells=REAL.cells + list({**CELLS, **MORE_CELLS}),
    serve_mixes=sorted(REAL.serve_mixes + [name for name, mix in _MIXES.items() if "loop" in mix]),
    closed=sorted(REAL.closed + [name for name, mix in _MIXES.items() if mix.get("loop") == "closed"]))

# Tests of the rule files that read the real tree and do not run on the copy, each with its reason.
NOT_ON_THE_COPY = {
    "test_chat_file_records_its_sweep": "reads chat-poisson's own sweep on the chip; a made-up mix was never swept",
    "test_longprompts_first_512_requests_are_those_of_the_supply_of_512":
        "one file's history (longprompt-closed ran at 512 and at 1,024); a made-up mix has none",
}


def over(arg: str, items):
    """Parametrise a rule over ``items(tree)``: here over the real tree's, under the ids they always had, and in
    ``copy_cases`` over the copy's."""
    def mark(rule):
        rule.over = items
        return pytest.mark.parametrize(arg, items(REAL))(rule)
    return mark


def copy_cases(namespace: dict) -> list:
    """For a test module's ``globals()``: a case for every rule of the module (a test that takes a ``tree``) and
    everything of the copy it runs over, for a test that takes ``rule``, ``item`` and the fixture ``appended``
    and calls ``rule(*item, tree=appended)``."""
    cases = []
    for name, rule in namespace.items():
        if (name.startswith("test_") and inspect.isfunction(rule) and "tree" in inspect.signature(rule).parameters
                and name not in NOT_ON_THE_COPY):
            items = [(i,) for i in rule.over(PLANNED)] if hasattr(rule, "over") else [()]
            cases += [pytest.param(rule, item, id="-".join([name[len("test_"):], *item])) for item in items]
    return cases


@pytest.fixture(scope="module")
def appended(tmp_path_factory) -> Tree:
    """The copy with cells appended: ``benchmark/`` is copied once a module, not once a test."""
    return Tree(appended_copy(tmp_path_factory.mktemp("appended")))
