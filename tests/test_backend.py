"""runtime/backend.py: where the compile cache goes, and that the control
plane stays off JAX (a process that has touched JAX holds the chip)."""

from __future__ import annotations

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
