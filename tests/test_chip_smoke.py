"""``chip_smoke.py`` and the no-fallback start-up path, as far as a
sandbox without an accelerator can show them: the rehearsal exercises
every assertion of the smoke at tiny size on the CPU; without the
rehearsal argument the script fails from its probe; and a ``tpu_chips``
job fails in its replica instead of training on the CPU.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SMOKE = [sys.executable, str(ROOT / "chip_smoke.py")]


def _env(**over):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(over)
    return env


def test_rehearsal_passes_and_cache_follows_the_environment(tmp_path):
    """Every phase and assertion of the smoke at tiny size — and, with
    JAX_COMPILATION_CACHE_DIR exported, every program the supervisor's
    children compile lands there and the supervisor makes no cache
    directory of its own."""
    cache = tmp_path / "xc"
    out = subprocess.run(
        SMOKE + ["--rehearse-cpu"],
        env=_env(
            JAX_PLATFORMS="cpu",
            JAX_COMPILATION_CACHE_DIR=str(cache),
        ),
        capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    lines = out.stdout.splitlines()
    assert lines[0] == "REHEARSAL platform=cpu"
    assert not [ln for ln in lines if ln.startswith('{"ok"')]
    assert "FAIL" not in out.stdout
    passed = [ln for ln in lines if ln.startswith("== ") and ": passed" in ln]
    # kernels, train, serve, fsdp, smoke_dist, gang
    assert len(passed) == 6, passed
    # At this size the stream is learnable, and the trainer must learn it
    # (the chip sizes cannot show that in twenty steps; see check_trained).
    first, final = re.search(r"loss ([\d.]+) -> ([\d.]+)", passed[1]).groups()
    assert float(final) < float(first) - 1.0
    assert all(f"compile cache {cache}:" in ln for ln in passed)
    assert any(cache.iterdir())
    state = ROOT / "chiprun_out" / "chip_smoke" / "state"
    assert state.is_dir() and not list(state.rglob("*xla_cache*"))


@pytest.mark.parametrize("platforms", [None, "cpu"])
def test_without_the_rehearsal_argument_it_fails_here(platforms):
    env = _env() if platforms is None else _env(JAX_PLATFORMS=platforms)
    out = subprocess.run(SMOKE, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "probe found no tpu device" in out.stdout


def test_tpu_chips_job_fails_without_a_chip(tmp_path):
    """`tpujob run` of a tpu_chips job, with JAX_PLATFORMS=cpu exported
    around the supervisor: the injected pin wins, backend creation raises
    in the replica, and the job ends Failed — it does not train on the
    CPU and exit 0."""
    job = tmp_path / "job.yaml"
    job.write_text(
        "api_version: tpujob.dev/v1\nkind: TPUJob\nmetadata: {name: nochip}\n"
        "spec:\n  replica_specs:\n    Master:\n      replicas: 1\n"
        "      template:\n"
        "        module: pytorch_operator_tpu.workloads.llama_train\n"
        '        args: ["--config", "tiny", "--steps", "2"]\n'
        "        resources: {tpu_chips: 1}\n"
        "  run_policy: {backoff_limit: 0}\n"
    )
    state = tmp_path / "state"
    out = subprocess.run(
        [sys.executable, "-m", "pytorch_operator_tpu.client.cli",
         "--state-dir", str(state), "run", str(job), "--timeout", "120"],
        env=_env(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT)),
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
    )
    assert out.returncode == 1, out.stdout + out.stderr
    assert "tpujob default/nochip: Failed" in out.stdout
    log = (state / "logs" / "default_nochip-master-0.log").read_text()
    assert "Unable to initialize backend 'tpu'" in log
    assert "[llama] config=" not in log  # never reached the model
