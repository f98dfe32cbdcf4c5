"""Profile-report tool (pytorch_operator_tpu/profiling.py).

A real workload writes a jax.profiler trace; the tool must parse the
xplane.pb and produce a self-time breakdown whose busy total does not
exceed the step span (the nesting bug it exists to avoid is
double-counting scan bodies inside their `while`).
"""

import subprocess
import sys

import pytest

import tests.jaxenv  # noqa: F401

from pytorch_operator_tpu import profiling
from pytorch_operator_tpu.workloads import llama_train


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    """A profiled run of the tiny trainer over the eight virtual devices,
    compiled HERE and not taken from the persistent compile cache: an
    XLA:CPU program whose devices meet in in-process collectives, once it
    comes back from the cache, now and then runs two of its collectives at
    once on the eight threads there are, seven devices wait in one and the
    eighth in the other, and after 40 s the runtime aborts the process
    (``rendezvous.cc``: "Termination timeout"; about one run in four here,
    none in twelve compiled fresh). Under ``-n 6`` this module's tests go to
    several workers, each runs this fixture, and every worker after the
    first found the first one's program in the cache (PR 39)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    d = tmp_path_factory.mktemp("prof")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        llama_train.run(
            config="tiny", batch_size=4, seq_len=32, steps=4, warmup=1,
            profile_dir=str(d), log=lambda *_: None,
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    return d


def test_report_parses_cpu_trace(trace_dir):
    report = profiling.device_report(trace_dir, device_substr="CPU")
    assert report is not None
    assert report.get("busy_s", 0) > 0
    assert report["categories"], report
    # Self-time accounting: total busy is a partition of the trace, so
    # the per-category sum equals busy (no nested double counting).
    total = sum(c["pct_of_busy"] for c in report["categories"])
    assert total == pytest.approx(100.0, abs=0.5), total


def test_report_missing_device_returns_none(trace_dir):
    assert profiling.device_report(trace_dir, device_substr="NOPE") is None


def test_cli_human_and_json(trace_dir):
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_operator_tpu.profiling",
         str(trace_dir), "--device", "CPU", "--top", "5"],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "by op category" in out.stdout
    j = subprocess.run(
        [sys.executable, "-m", "pytorch_operator_tpu.profiling",
         str(trace_dir), "--device", "CPU", "--json", "--top", "3"],
        capture_output=True, text=True,
    )
    assert j.returncode == 0, j.stderr
    import json

    data = json.loads(j.stdout)
    assert len(data["top_ops"]) <= 3


def test_missing_dir_errors_cleanly(tmp_path):
    rc = profiling.main([str(tmp_path / "nope")])
    assert rc == 1


def test_report_needs_no_tensorflow(trace_dir):
    """The reader is ``jax.profiler.ProfileData``: the report comes out with
    TensorFlow made unimportable, and lists the program's own spans."""
    code = (
        "import sys; sys.modules['tensorflow'] = None; sys.modules['tsl'] = None\n"
        "from pytorch_operator_tpu import profiling\n"
        f"sys.exit(profiling.main([{str(trace_dir)!r}, '--device', 'host:CPU', '--json', '--top', '400']))\n"
    )
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    import json

    ops = {r["op"] for r in json.loads(out.stdout)["top_ops"]}
    assert "step" in ops  # obs.span("step") of the trainer, mirrored into the trace
