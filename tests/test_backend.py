"""runtime/backend.py: where the compile cache goes, and that the control
plane stays off JAX (a process that has touched JAX holds the chip)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from pytorch_operator_tpu.runtime.backend import compile_cache_dir

ROOT = Path(__file__).resolve().parents[1]


def test_cache_dir_is_the_environments_or_the_checkouts(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # Fixed, inside the checkout, whatever the current directory is.
    monkeypatch.chdir(tmp_path)
    assert compile_cache_dir() == str(ROOT / ".xla_cache")
    assert ".xla_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def _setup_backend_in_child(env_extra: dict, cwd) -> list:
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT), **env_extra)
    out = subprocess.run(
        [sys.executable, "-c",
         "from pytorch_operator_tpu.runtime.backend import setup_backend\n"
         "import jax\n"
         "setup_backend()\n"
         "print(jax.config.jax_compilation_cache_dir)\n"
         "print(jax.config.jax_enable_compilation_cache)"],
        env=env, cwd=cwd, capture_output=True, text=True, timeout=120, check=True,
    )
    return out.stdout.split()


def test_setup_backend_places_the_cache(tmp_path):
    """In-process runs and the supervisor's children both go through
    setup_backend: the exported directory wins; unset, the fixed
    in-checkout path; and a multi-process CPU (gloo) world keeps the
    cache off."""
    assert _setup_backend_in_child({}, tmp_path) == [str(ROOT / ".xla_cache"), "True"]
    assert _setup_backend_in_child(
        {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, tmp_path
    ) == ["/some/dir", "True"]
    assert _setup_backend_in_child({"TPUJOB_NUM_PROCESSES": "2"}, tmp_path)[1] == "False"


COUNTING_REPLICA = """
from pytorch_operator_tpu.runtime import backend, rendezvous
backend.setup_backend()
import jax, jax.numpy as jnp
x = jnp.arange(64.0).reshape(8, 8)      # eager operations: a small program each
jax.jit(lambda a: jnp.tanh(a) @ a.T)(x).block_until_ready()
rendezvous.report_first_step(0)
jax.jit(lambda a: a.sum())(x).block_until_ready()
rendezvous.report_metrics(1, loss=0.0)
"""


def _counted_records(env_extra: dict, tmp_path) -> dict:
    """Run the script above as a replica would run and return its status
    records by event (counted, never timed)."""
    status = tmp_path / "status"
    status.mkdir(exist_ok=True)
    path = status / "master-0.jsonl"
    path.unlink(missing_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT),
               TPUJOB_STATUS_DIR=str(status), **env_extra)
    subprocess.run([sys.executable, "-c", COUNTING_REPLICA], env=env, cwd=tmp_path,
                   capture_output=True, timeout=180, check=True)
    return {r["event"]: r for r in map(json.loads, path.read_text().splitlines())}


def test_a_warm_start_compiles_nothing(tmp_path):
    """setup_backend keeps every compiled program, however fast it compiled
    (JAX's own threshold is a second), and counts: a second process finds
    every program of the first in the cache. The counts ride on the
    replica's first_step and metrics records."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xc")}
    cold = _counted_records(env, tmp_path)
    assert cold["first_step"]["programs_from_cache"] == 0
    assert 0 < cold["first_step"]["programs_compiled"] < cold["metrics"]["programs_compiled"]
    warm = _counted_records(env, tmp_path)
    for event in ("first_step", "metrics"):
        assert warm[event]["programs_compiled"] == 0
        assert warm[event]["programs_from_cache"] == cold[event]["programs_compiled"]
    assert warm["metrics"]["loss"] == 0.0


def test_the_gloo_world_neither_caches_nor_counts(tmp_path):
    """A multi-process CPU world runs without the cache (setup_backend
    says why), so its records claim nothing about it."""
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xc"), "TPUJOB_NUM_PROCESSES": "2"}
    recs = _counted_records(env, tmp_path)
    assert "programs_compiled" not in recs["first_step"]
    assert "programs_from_cache" not in recs["metrics"]
    assert not (tmp_path / "xc").exists()


def test_control_plane_imports_no_jax():
    """The CLI and the supervisor start replicas that need the chip; if
    importing them pulled in JAX, a later backend query in the parent
    would take the chip from its own children."""
    subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import pytorch_operator_tpu.client.cli\n"
         "import pytorch_operator_tpu.controller.supervisor\n"
         "sys.exit('jax' in sys.modules)"],
        env={"PYTHONPATH": str(ROOT), "PATH": "/usr/bin:/bin"},
        timeout=120, check=True,
    )
