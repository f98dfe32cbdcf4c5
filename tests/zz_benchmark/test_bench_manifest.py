"""BENCHMARK.json against the contract's lexical rules, and every cell's
files found by name: of the real tree, and of a copy with cells appended
(``rules.py``)."""

from __future__ import annotations

import re

import pytest

from tests.zz_benchmark.benchcells import MORE_CELLS
from tests.zz_benchmark.rules import PLANNED, REAL, appended, copy_cases, over  # noqa: F401

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
GROUPS = ["configs", "workloads", "end_to_end", "per_layer"]


def test_top_level_keys_and_limits(tree=REAL):
    manifest = tree.manifest
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert len((tree.root / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert all(not w.startswith("/") and ".." not in w for w in manifest["command"])
    for p in manifest["paths"]:
        assert (tree.root / p).is_dir()
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(tree.cells) // 4)


@over("group", lambda tree: GROUPS)
def test_names_units_and_keys(group, tree=REAL):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[group]
    names = [e["name"] for e in tree.manifest[group]]
    assert len(names) == len(set(names))
    for e in tree.manifest[group]:
        assert set(e) <= allowed and NAME.match(e["name"]), e
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] and "\t" not in e[key]
        if "unit" in e:
            assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
        if group == "end_to_end":
            assert e["source"] in ("host_clock", "device_trace") and 0.01 <= e["bound"] <= 0.1
        if group == "per_layer":
            assert e["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
            assert e["moves"] in tree.e2e


@over("cell", lambda tree: tree.cells)
def test_every_cells_files_are_found_by_name(cell, tree=REAL):
    from benchmark import family, run

    manifest = tree.manifest
    spec = run.load_cell(cell, tree.bench)
    w = spec["cell"]
    assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    cfg = spec["config"]
    for key in ("source", "reduced", "assumed", "deployment", "bench"):
        assert key in cfg, f"{w['config']}: the configuration file states no {key!r}"
    entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]
    assert str(entry["file"]).startswith(tuple(manifest["paths"]))
    assert spec["limits"].get("limits"), "the cell's limits file is missing or empty"
    family_dir = tree.bench / "families" / cfg["bench"]["family"]
    assert all((family_dir / f"{part}.py").is_file() for part in family.PARTS), f"{family_dir}: not a family"
    reported = [m for m in run.metrics_of(manifest, "end_to_end", cell)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    layer = run.metrics_of(manifest, "per_layer", cell)
    assert layer
    for m in layer:
        assert (tree.bench / "layer_metrics" / f"{m['name']}.py").is_file()
        assert cell in tree.e2e[m["moves"]].get("workloads", tree.cells), f"{m['name']} moves a metric {cell} does not report"


def test_every_configuration_is_used_and_no_width_is_reduced(tree=REAL):
    manifest = tree.manifest
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    widths = re.compile(r"hidden_size|intermediate|latent|state_size|_dim$|_rank$|head_dim|expansion|experts_per_tok")
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16 and not any(widths.search(k) for k in c["reduced"])
    assert "ttft_p75_ms" not in tree.e2e and "setup_s" in tree.e2e


# ---- the same rules, on a copy with cells appended ----


@pytest.mark.parametrize("rule, item", copy_cases(globals()))
def test_the_rule_holds_on_a_copy_with_cells_appended(rule, item, appended):
    rule(*item, tree=appended)


def test_the_copy_holds_what_its_cases_were_made_from(appended):
    """The cases above were made while pytest collected, before any copy was: the copy holds exactly those
    cells and mixes, the benchmark's own among them, and what ``MORE_CELLS`` is there to bring."""
    assert (appended.cells, appended.serve_mixes, appended.closed) == (PLANNED.cells, PLANNED.serve_mixes, PLANNED.closed)
    assert set(REAL.cells) < set(appended.cells) and set(REAL.mixes) < set(appended.mixes)
    closed = {w["name"]: w["traffic"] for w in appended.manifest["workloads"] if w["name"] in appended.closed_cells}
    assert set(MORE_CELLS) < set(closed) and len(closed) == len(REAL.closed_cells) + 1 + len(MORE_CELLS)
    assert closed["tiny-long-2"] == closed["tiny-long"] and closed["tiny-long-b"] not in REAL.closed + [closed["tiny-long"]]
    deep = appended.mixes[closed["tiny-deep"]]
    (cell,) = [w for w in appended.manifest["workloads"] if w["name"] == "tiny-deep"]
    assert max(p + a for p, a in deep["lengths"]) == 6000 and deep["check_pad_to"] == 6144
    assert appended.config_of(cell)["bench"]["engine"]["max_decode_len"] == 8192
