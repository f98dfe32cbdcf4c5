"""The benchmark's tests that start processes, serving part (the training
part is ``test_bench_runs_train.py``, the controls ``test_bench_controls.py``:
the driver's workers take a file each, so at most two replica trees and one
control run beside the suite's timing tests, each single-threaded, niced
and on a core of its own).

- a whole run of a made-up tiny open-loop cell on the CPU (added as files
  and entries only): correct; a closed-loop one with the timed path broken
  underneath (tokens altered where they are produced): not correct;
- the measurement path refuses a CPU and says why.
"""

from __future__ import annotations

import json
import shutil

from tests.zz_benchmark.benchproc import ROOT, run, tiny_cell


def test_made_up_open_loop_cell_runs_and_is_correct(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-chat")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] == 12  # 4 a second for 3 s
    assert set(res["metrics"]) == {"ttft_mean_ms", "tpot_p50_ms", "setup_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    # Each number compared stands beside its limit, last on the line.
    assert list(res)[-1] == "compared" and res["compared"]["served_logit_gap_max"]["limit"] == 0.05
    assert res["compared"]["requests_answered_in_full"] == {"value": 12, "limit": 12}
    assert "compared served_logit_gap_max" in out and "generator lateness" in out and "generator sleeps that overran" in out


def test_altered_tokens_are_not_correct_and_the_closed_loop_reports_tokens_per_s(tmp_path):
    rc, out, res = tiny_cell(tmp_path, "tiny-long-broken", module="tests.zz_benchmark.broken_serve")
    assert rc == 0 and res, out[-3000:]
    assert res["correct"] is False and "NOT CORRECT" in out
    assert res["failed"] == 0 and res["attempted"] >= 2  # every request was answered in full; the answers are wrong
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert res["metrics"]["serve_tokens_per_s"]["value"] > 0 and "answers seen whole inside the window" in out


CELL = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]["name"]


def test_a_run_without_a_chip_fails_and_says_why():
    rc, out = run(["benchmark/run.py", "--workload", CELL, "--seed", "5", "--seconds", "1", "--trace", "0"],
                  timeout=240, env={"JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"})
    assert rc != 0
    assert "benchmark: no result:" in out
    assert not [ln for ln in out.splitlines() if ln.startswith('{"correct"')]


def test_no_result_without_the_system_under_test(tmp_path):
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    rc, out = run([str(tmp_path / "benchmark" / "run.py"), "--workload", CELL, "--seed", "5", "--seconds", "1",
                   "--trace", "0"], timeout=60)
    assert rc != 0 and "not in this checkout" in out


def test_unknown_workload_is_refused():
    rc, out = run(["benchmark/run.py", "--workload", "no-such-cell", "--seed", "1", "--seconds", "1"], timeout=60)
    assert rc != 0 and "no workload" in out
