"""Pre-warmed standby replicas — the schedule-to-first-step accelerator.

Even a warm (compile-cached) job start pays process spawn + ``import
jax`` (and friends) + backend init, serially, before the workload's first
line runs.
The reference has no analog (kubelet image pulls / container starts are
its version of this cost, and it never attacks them); this is TPU-native
performance work on the BASELINE.json:2 north-star metric.

Design: the supervisor keeps N **standby** processes that have already
paid the interpreter + heavy-import cost (jax/flax/optax/numpy — NO
device client: a chip belongs to one process at a time, so a standby
that opened it would take it from the live jobs; the client is acquired
lazily after assignment). ``SubprocessRunner.create`` hands a job to a
ready standby
instead of spawning cold:

1. runner writes ``<id>.assign.json`` (atomic tmp+rename) into the pool
   dir and waits briefly for the claim ack;
2. the standby (polling) renames it to ``<id>.assign.claimed``, applies
   the injected env wholesale, re-applies the jax options whose env vars
   were already consumed at import (config.update), redirects
   stdout/stderr onto the replica's log file, and runs the template
   module in-process via ``runpy`` as ``__main__``;
3. on completion it writes the exit-capture file (same protocol as the
   cold path's sh wrapper) and exits with the workload's code.

One job per standby — the process dies with its job and the pool
replenishes on the next sync pass, so replica isolation semantics are
unchanged: the handle's pid IS the workload's pid, signals/kill
escalation/adoption all behave exactly as for cold spawns. Only
``module`` templates are eligible (exec'ing an arbitrary ``command``
argv would discard the warm imports); anything else falls back to a cold
spawn, as does an assignment whose ack times out (standby died between
readiness check and claim).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

# jax options whose environment variables are read ONCE at import time:
# the standby imported jax long before the job's env existed, so these
# must be re-applied through jax.config after the env lands.
_JAX_ENV_CONFIG = (
    ("JAX_COMPILATION_CACHE_DIR", "jax_compilation_cache_dir"),
    ("JAX_PLATFORMS", "jax_platforms"),
)


# ---- the standby process ----


def _preimport() -> None:
    """Pay the heavy imports up front. Deliberately NO jax.devices() /
    backend creation — device acquisition stays lazy (contention)."""
    import numpy  # noqa: F401
    import jax  # noqa: F401
    import flax.linen  # noqa: F401
    import optax  # noqa: F401


def _run_assignment(spec: dict) -> int:
    """Become the replica: env, log redirect, cwd, run the module."""
    import runpy
    import traceback

    env = spec.get("env") or {}
    os.environ.clear()
    os.environ.update(env)
    # PYTHONPATH was consumed by the interpreter at standby startup; the
    # job's entries must land on sys.path too, or a module that imports
    # fine on the cold path ImportErrors on the warm one.
    for entry in reversed(env.get("PYTHONPATH", "").split(os.pathsep)):
        if entry and entry not in sys.path:
            sys.path.insert(0, entry)
    import jax

    for env_key, cfg_key in _JAX_ENV_CONFIG:
        if env.get(env_key):
            jax.config.update(cfg_key, env[env_key])
    # Route all output to the replica's log file (kubectl-logs analog) —
    # fd-level dup2 so subprocesses and C extensions follow too.
    log_fd = os.open(
        spec["log_path"], os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644
    )
    sys.stdout.flush()
    sys.stderr.flush()
    os.dup2(log_fd, 1)
    os.dup2(log_fd, 2)
    os.close(log_fd)
    if spec.get("cwd"):
        os.chdir(spec["cwd"])
    sys.argv = [spec["module"]] + list(spec.get("args") or [])
    code = 0
    try:
        runpy.run_module(spec["module"], run_name="__main__", alter_sys=True)
    except SystemExit as e:
        if isinstance(e.code, int):
            code = e.code
        elif e.code is not None:
            print(e.code, file=sys.stderr)
            code = 1
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # Exit-capture protocol (same file the cold path's sh wrapper writes).
    try:
        ef = spec["exit_path"]
        tmp = ef + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(code))
        os.replace(tmp, ef)
    except OSError:
        pass
    return code


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--dir", required=True, help="pool directory")
    p.add_argument("--id", required=True, help="this standby's id")
    p.add_argument(
        "--parent", type=int, default=None,
        help="supervisor pid: exit when reparented away from it",
    )
    args = p.parse_args(argv)
    pool = Path(args.dir)
    assign = pool / f"{args.id}.assign.json"
    claimed = pool / f"{args.id}.assign.claimed"
    _preimport()
    ready_tmp = pool / f"{args.id}.ready.tmp"
    ready_tmp.write_text(str(os.getpid()))
    ready_tmp.replace(pool / f"{args.id}.ready")
    while True:
        # Orphan guards: a supervisor that died without shutdown() (crash,
        # SIGKILL) must not leak a 50 Hz poll loop pinning jax-sized RSS
        # forever. Reparenting away from the RECORDED parent pid (not a
        # bare ppid==1 test, which would misfire when the supervisor
        # itself is pid 1 in a container) or the pool dir vanishing both
        # mean the pool is gone.
        if not pool.is_dir() or (
            args.parent is not None and os.getppid() != args.parent
        ):
            return 0
        if assign.exists():
            try:
                spec = json.loads(assign.read_text())
            except (OSError, ValueError):
                # invariant: waived — 10ms paced re-read of an assign file caught mid-rename, not a retry loop
                time.sleep(0.01)
                continue
            try:
                assign.replace(claimed)  # the ack the runner waits on
            except OSError:
                return 0  # pool dir torn down underneath us
            return _run_assignment(spec)
        time.sleep(0.02)


# ---- the supervisor-side pool ----


class StandbyPool:
    """Spawn/track/assign standby processes (supervisor side).

    Thread-safe; ``replenish()`` is called from the runner's sync pass.
    Standbys consume no scheduler slots — they hold no devices.
    """

    ACK_TIMEOUT_S = 2.0

    def __init__(self, state_dir: Path, size: int):
        self.dir = Path(state_dir) / "standby"
        self.dir.mkdir(parents=True, exist_ok=True)
        self.log_dir = Path(state_dir) / "logs"
        self.log_dir.mkdir(parents=True, exist_ok=True)
        # Crash-loop backoff: a standby that dies before ever reaching
        # READY (broken env, jax ImportError) must not re-pay a full
        # interpreter+jax import every sync pass forever.
        self._fail_streak = 0
        self._not_before = 0.0
        self.size = size
        self._procs: Dict[str, subprocess.Popen] = {}
        self._counter = 0
        self._lock = threading.Lock()

    def _files(self, sid: str):
        return [
            self.dir / f"{sid}{suffix}"
            for suffix in (".ready", ".assign.json", ".assign.claimed")
        ]

    def _spawn_one(self) -> bool:
        sid = f"s{os.getpid()}-{self._counter}"
        self._counter += 1
        env = dict(os.environ)
        pkg_root = str(Path(__file__).resolve().parents[2])
        parts = [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        if pkg_root not in parts:
            parts.insert(0, pkg_root)
        env["PYTHONPATH"] = os.pathsep.join(parts)
        env["PYTHONUNBUFFERED"] = "1"
        log_f = open(self.log_dir / f"standby-{sid}.log", "ab")
        try:
            proc = subprocess.Popen(
                [
                    sys.executable, "-m",
                    "pytorch_operator_tpu.controller.standby",
                    "--dir", str(self.dir), "--id", sid,
                    "--parent", str(os.getpid()),
                ],
                env=env,
                stdout=log_f,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )
        except OSError:
            log_f.close()
            return False
        log_f.close()  # the child owns the fd now
        self._procs[sid] = proc
        return True

    def set_size(self, size: int) -> None:
        """Retarget the pool (takes effect on the next replenish; shrink
        does not kill live standbys). size=0 pauses replenishment — e.g.
        while a latency measurement must not share the host core with a
        fresh standby's import burst."""
        with self._lock:
            self.size = size

    def replenish(self) -> None:
        """Reap dead standbys, top the pool back up to ``size``.

        Crash-looping standbys back off exponentially (up to 60s between
        spawn attempts): each reap of a standby that died before ever
        reaching READY doubles the wait; any standby reaching READY
        resets it. Dead standbys' log files are rotated into ONE
        ``standby-last-failure.log`` (nonzero exits) or deleted (clean
        exits) — a long-lived daemon must not grow logs/ unboundedly.
        """
        with self._lock:
            for sid, proc in list(self._procs.items()):
                if proc.poll() is not None:
                    self._procs.pop(sid)
                    was_ready = (self.dir / f"{sid}.ready").exists()
                    for f in self._files(sid):
                        f.unlink(missing_ok=True)
                    log = self.log_dir / f"standby-{sid}.log"
                    if proc.returncode != 0:
                        # Keep exactly one failure log for diagnosis.
                        try:
                            log.replace(self.log_dir / "standby-last-failure.log")
                        except OSError:
                            log.unlink(missing_ok=True)
                    else:
                        log.unlink(missing_ok=True)
                    if not was_ready:
                        self._fail_streak += 1
                        delay = min(60.0, 2.0 ** min(self._fail_streak, 6))
                        # monotonic: an NTP step must not collapse the
                        # crash-loop holdoff (respawn storm) or stretch
                        # it (pool stays empty for minutes).
                        self._not_before = time.monotonic() + delay
                        print(
                            f"[standby] {sid} died (exit {proc.returncode}) "
                            f"before READY — backing off {delay:.0f}s "
                            f"(see logs/standby-last-failure.log)",
                            file=sys.stderr,
                        )
            if any(
                (self.dir / f"{sid}.ready").exists() for sid in self._procs
            ):
                self._fail_streak = 0
            if time.monotonic() < self._not_before:
                return
            # Bounded: a persistent spawn failure (fork limit, ENOMEM)
            # must not busy-loop under the pool lock — try once per
            # missing slot, retry on the next sync pass.
            for _ in range(max(self.size - len(self._procs), 0)):
                if not self._spawn_one():
                    break

    def ready_count(self) -> int:
        with self._lock:
            return sum(
                1
                for sid, proc in self._procs.items()
                if proc.poll() is None and (self.dir / f"{sid}.ready").exists()
            )

    def take(self) -> Optional[Tuple[str, subprocess.Popen]]:
        """Pop a ready, live standby (or None). The caller MUST follow
        with assign() or kill()."""
        with self._lock:
            for sid, proc in list(self._procs.items()):
                if proc.poll() is None and (self.dir / f"{sid}.ready").exists():
                    self._procs.pop(sid)
                    # Reaching READY proves the spawn path works — reset
                    # the crash-loop backoff here too, not only when a
                    # replenish pass happens to observe the ready marker
                    # (a standby claimed between passes, or a pool that
                    # drains to empty, would otherwise leave a stale
                    # streak that jumps one later pre-READY death
                    # straight to the capped backoff).
                    self._fail_streak = 0
                    self._not_before = 0.0
                    return sid, proc
        return None

    def assign(self, sid: str, proc: subprocess.Popen, spec: dict) -> bool:
        """Hand a job spec to a taken standby; True once the standby
        acked the claim. On timeout (it died under us) the standby is
        killed and False returned — the caller cold-spawns instead."""
        tmp = self.dir / f"{sid}.assign.json.tmp"
        target = self.dir / f"{sid}.assign.json"
        claimed = self.dir / f"{sid}.assign.claimed"
        try:
            tmp.write_text(json.dumps(spec))
            tmp.replace(target)
        except OSError:
            self.kill(sid, proc)
            return False
        # monotonic: the ACK window is a within-process budget; a clock
        # step here would either kill a healthy standby mid-claim or
        # stall assignment on a dead one.
        deadline = time.monotonic() + self.ACK_TIMEOUT_S
        while time.monotonic() < deadline:
            if claimed.exists():
                claimed.unlink(missing_ok=True)
                # The sid leaves the pool here: drop its ready marker AND
                # its pre-handoff log (output goes to the replica's own
                # log from the claim's dup2 onward) so a long-lived
                # daemon doesn't leak files per warm job.
                (self.dir / f"{sid}.ready").unlink(missing_ok=True)
                (self.log_dir / f"standby-{sid}.log").unlink(missing_ok=True)
                return True
            if proc.poll() is not None:
                break
            time.sleep(0.01)
        self.kill(sid, proc)
        target.unlink(missing_ok=True)
        return False

    def kill(self, sid: str, proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, 9)
            except (ProcessLookupError, PermissionError):
                pass
        for f in self._files(sid):
            f.unlink(missing_ok=True)
        (self.log_dir / f"standby-{sid}.log").unlink(missing_ok=True)

    def shutdown(self) -> None:
        """Kill every idle standby (assigned ones became job replicas and
        belong to the runner's normal teardown path)."""
        with self._lock:
            for sid, proc in list(self._procs.items()):
                self.kill(sid, proc)
            self._procs.clear()


if __name__ == "__main__":
    sys.exit(main())
