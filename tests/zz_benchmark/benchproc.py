"""Run a child of a benchmark test under a time limit of its own, in a
session of its own, and end all of it when the limit passes."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


# The children are JAX processes three levels deep; one device and one
# compute thread each, at a low priority and all on one core (``core``
# counts from the machine's last), keeps them from crowding the suite's
# timing tests. They keep a compile cache of their own, so that executables
# built under these flags never reach the suite's workers through the
# checkout's shared ``.xla_cache``, nor the workers' these children.
QUIET = {"XLA_FLAGS": "--xla_force_host_platform_device_count=1 --xla_cpu_multi_thread_eigen=false",
         "OMP_NUM_THREADS": "1", "JAX_COMPILATION_CACHE_DIR": str(ROOT / ".benchrun" / "xla_cache")}


def _step_aside(core):
    os.nice(15)
    os.sched_setaffinity(0, {sorted(os.sched_getaffinity(0))[core]})


def run(args, timeout, env=None, core=-1):
    proc = subprocess.Popen(
        [sys.executable, *args], cwd=ROOT, env={**os.environ, **QUIET, **(env or {})}, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, start_new_session=True,
        preexec_fn=lambda: _step_aside(core),
    )
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        raise AssertionError(f"child exceeded {timeout}s:\n{out[-3000:]}")
    return proc.returncode, out


def tiny_cell(tmp_path, cell, seconds=3.0, module=None, timeout=300, core=-1):
    """One CPU run of a made-up tiny cell; returns (rc, output, result)."""
    args = ["-m", "tests.zz_benchmark.benchcells", str(tmp_path / "copy"), cell, str(seconds)]
    rc, out = run(args + ([module] if module else []), timeout, core=core)
    last = out.strip().splitlines()[-1] if out.strip() else ""
    return rc, out, json.loads(last) if last.startswith("{") else None


def control(kind, core):
    """One reading of ``controls.py`` (the tiny cell's control, the reference
    in the next precision down; or the drivers' contract), on one large seed."""
    rc, out = run(["-m", "tests.zz_benchmark.controls", kind, str(2**31 + 5)], timeout=240,
                  env={"JAX_PLATFORMS": "cpu"}, core=core)
    assert rc == 0, out[-2000:]
    return json.loads(out.strip().splitlines()[-1])
