"""The controls at the tiny size (see ``test_bench_runs.py``): the reference
in the next precision down, put in the program's place, fails the tiny
cells' limits. One large seed each; the chip readings behind the real
cells' limits are in ``benchmark/limits/``.
"""

from __future__ import annotations

from tests.zz_benchmark.benchcells import CELLS
from tests.zz_benchmark.benchproc import control

CORE = -3  # the whole runs keep the last two cores


def test_int4_weights_fail_the_serving_limit():
    numbers = control("serve", core=CORE)
    assert numbers["positions"] == 28 and numbers["smallest"] >= 0.0
    assert numbers["served_logit_gap_max"] > 3 * CELLS["tiny-chat"][3]["served_logit_gap_max"], numbers


def test_fp8_operands_fail_a_training_limit():
    numbers, limits = control("train", core=CORE), CELLS["tiny-pre"][3]
    assert set(numbers) == set(limits)
    assert any(numbers[n] > limits[n] for n in limits), numbers
