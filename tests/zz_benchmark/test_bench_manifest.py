"""BENCHMARK.json against the contract's lexical rules, and every cell's
files found by name."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
E2E = {m["name"]: m for m in MANIFEST["end_to_end"]}


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert all(not w.startswith("/") and ".." not in w for w in MANIFEST["command"])
    for p in MANIFEST["paths"]:
        assert (ROOT / p).is_dir()
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_keys(group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
    }[group]
    names = [e["name"] for e in MANIFEST[group]]
    assert len(names) == len(set(names))
    for e in MANIFEST[group]:
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
            assert e["moves"] in E2E


@pytest.mark.parametrize("cell", CELLS)
def test_every_cells_files_are_found_by_name(cell):
    from benchmark import family, run

    spec = run.load_cell(cell)
    w = spec["cell"]
    assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    cfg = spec["config"]
    for key in ("source", "reduced", "assumed", "deployment", "bench"):
        assert key in cfg, f"{w['config']}: the configuration file states no {key!r}"
    entry = next(c for c in MANIFEST["configs"] if c["name"] == w["config"])
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) and entry["source"] == cfg["source"]
    assert str(entry["file"]).startswith(tuple(MANIFEST["paths"]))
    assert spec["limits"].get("limits"), "the cell's limits file is missing or empty"
    family_dir = ROOT / "benchmark" / "families" / cfg["bench"]["family"]
    assert all((family_dir / f"{part}.py").is_file() for part in family.PARTS), f"{family_dir}: not a family"
    reported = [m for m in run.metrics_of(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    layer = run.metrics_of(MANIFEST, "per_layer", cell)
    assert layer
    for m in layer:
        assert (ROOT / "benchmark" / "layer_metrics" / f"{m['name']}.py").is_file()
        assert cell in E2E[m["moves"]].get("workloads", CELLS), f"{m['name']} moves a metric {cell} does not report"


def test_every_configuration_is_used_and_no_width_is_reduced():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    widths = re.compile(r"hidden_size|intermediate|latent|state_size|_dim$|_rank$|head_dim|expansion|experts_per_tok")
    for c in MANIFEST["configs"]:
        assert len(c["reduced"]) <= 16 and not any(widths.search(k) for k in c["reduced"])
    assert "ttft_p75_ms" not in E2E and "setup_s" in E2E
