"""The controls at the tiny size (see ``test_bench_runs.py``): the reference
in the next precision down, put in the program's place, fails the tiny
cells' limits, and every family's files meet the contract of
``benchmark/family.py`` and ``benchmark/reference_run.py``. One large seed each; the chip readings behind the real
cells' limits are in ``benchmark/limits/``.
"""

from __future__ import annotations

import ast

import pytest

from benchmark import family, reference_run
from tests.zz_benchmark.benchcells import CELLS, FAMILIES
from tests.zz_benchmark.benchproc import ROOT, control

FAMILY_DIRS = sorted(p for p in [*(ROOT / "benchmark" / "families").iterdir(), *FAMILIES.iterdir()]
                     if p.is_dir() and not p.name.startswith("_"))  # ``_common.py`` and ``__pycache__`` are no family

CORE = -3  # the whole runs keep the last two cores


def test_int4_weights_fail_the_serving_limit():
    numbers = control("serve", core=CORE)
    assert numbers["positions"] == 28 and numbers["smallest"] >= 0.0
    assert numbers["served_logit_gap_max"] > 3 * CELLS["tiny-chat"][3]["served_logit_gap_max"], numbers


def test_fp8_operands_fail_a_training_limit():
    numbers, limits = control("train", core=CORE), CELLS["tiny-pre"][3]
    assert set(numbers) == set(limits)
    assert any(numbers[n] > limits[n] for n in limits), numbers


@pytest.mark.parametrize("folder", FAMILY_DIRS, ids=lambda p: p.name)
def test_a_family_brings_its_four_files_and_its_reference_the_two_drivers(folder):
    for part in family.PARTS:
        assert (folder / f"{part}.py").is_file(), f"family {folder.name}: no {part}.py"
    defined = lambda part: {n.name: [a.arg for a in n.args.args] for n in
                            ast.parse((folder / f"{part}.py").read_text()).body if isinstance(n, ast.FunctionDef)}
    reference = defined("reference")
    assert reference.get("serve_check") == ["check", "control"] and reference.get("train_check") == ["check", "control"]
    assert "install" in defined("install") and "train_flops_per_token" in defined("flops")
    source = (folder / "reference.py").read_text()
    assert "pytorch_operator_tpu" not in source, "the reference imports the program"
    names = ast.dump(ast.parse((folder / "weights.py").read_text()))
    assert all(f"'{n}'" in names for n in ("dims", "make_params", "make_layer", "make_outer"))
    assert "reaches into the program" in ast.get_docstring(ast.parse((folder / "install.py").read_text())), \
        "install.py's docstring states where it reaches into the program"


def test_the_drivers_return_the_keys_the_contract_states():
    got = control("contract", core=CORE)
    assert got["serve"] == sorted(reference_run.SERVE_KEYS + reference_run.SERVE_CONTROL_KEYS + ("seconds", "platform"))
    assert got["positions"] == 13
    assert got["train"] == sorted(reference_run.TRAIN_KEYS + ("control", "seconds", "platform"))
    assert got["control"] == sorted(reference_run.TRAIN_KEYS)
    leaves = got["leaves"][0]
    assert leaves == got["leaves"][1] and "layers/attn/q_proj/kernel" in leaves and "lm_head/kernel" in leaves
