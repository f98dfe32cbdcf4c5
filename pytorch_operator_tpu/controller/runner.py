"""Replica process runners — the pod-control analog.

Reference: pod creation/deletion via ``podControl`` and the kubelet actually
running containers (SURVEY.md §3.2–3.3). Locally a *replica* is an OS
process. Two runners share one interface:

- :class:`SubprocessRunner` — the real thing: ``subprocess.Popen`` with
  injected env, per-replica log files, termination with escalation.
- :class:`FakeRunner` — the fake-clientset analog (SURVEY.md §4): records
  create/delete actions, and tests drive phases by hand
  (``set_phase(name, FAILED, exit_code=137)``) — no processes involved.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from ..api.types import ProcessTemplate, ReplicaPhase, ReplicaType
from .store import key_to_fs


def replica_name(job_key: str, rtype: ReplicaType, index: int) -> str:
    """Canonical replica name: ``<ns>/<job>-<type>-<index>`` (pod-name analog)."""
    return f"{job_key}-{rtype.value.lower()}-{index}"


def _proc_stat(pid: int):
    """(start_ticks, state, pgrp) from ``/proc/<pid>/stat``, or None if gone.

    The comm field (2) may contain spaces/parens, so split after the LAST
    ``)``. start_ticks (field 22) uniquely stamps a pid incarnation —
    the guard against pid reuse when adopting persisted records.
    """
    try:
        # Binary read: comm is arbitrary bytes (prctl PR_SET_NAME), so a
        # text-mode open could raise UnicodeDecodeError on a host process
        # we merely scanned past.
        with open(f"/proc/{pid}/stat", "rb") as f:
            raw = f.read()
    except OSError:
        return None
    rest = raw[raw.rfind(b")") + 2 :].split()
    return int(rest[19]), rest[0].decode("ascii"), int(rest[2])


def _pid_alive(pid: Optional[int], start_ticks: Optional[int]) -> bool:
    """Is this exact process incarnation still running (zombies count as
    dead — an orphan reparented to a non-reaping pid 1 stays 'Z')?"""
    if pid is None:
        return False
    stat = _proc_stat(pid)
    if stat is None or stat[1] == "Z":
        return False
    return start_ticks is None or stat[0] == start_ticks


def _group_members_alive(pgid: int) -> bool:
    """Any non-zombie process left in this process group? The exit-capture
    wrapper dies instantly on SIGTERM, so the wrapper's own exit proves
    nothing about the replica underneath — liveness and termination must be
    judged on the whole group. (A pid number stays allocated while it is a
    live pgid, so members found here are ours, not a pid-reuse stranger —
    up to the unavoidable full-wraparound edge once the group empties.)"""
    return pgid in _live_pgids()


def _live_pgids() -> set:
    """One /proc pass: the set of process groups with a non-zombie member."""
    out = set()
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        stat = _proc_stat(int(d))
        if stat is not None and stat[1] != "Z":
            out.add(stat[2])
    return out


def _replica_alive(
    pid: Optional[int], start_ticks: Optional[int], live_pgids: Optional[set] = None
) -> bool:
    """Replica liveness = wrapper pid alive OR any group member alive (a
    TERM-trapping replica can outlive its wrapper).

    Ordering matters for the pid-reuse guard: a LIVE pid with mismatched
    start ticks proves the pid was recycled to a stranger (our whole group
    must have emptied for the kernel to free the number), so the group
    check applies only when the wrapper pid itself is dead/zombie.
    ``live_pgids`` lets a caller amortize the /proc pass over many replicas.
    """
    if pid is None:
        return False
    stat = _proc_stat(pid)
    if stat is not None and stat[1] != "Z":
        return start_ticks is None or stat[0] == start_ticks
    if live_pgids is not None:
        return pid in live_pgids
    return _group_members_alive(pid)


# Wrapper that records the replica's exit code to a file the supervisor can
# read after a restart (the pod-status analog: exit codes survive the
# controller). The child runs in the wrapper's process group; a group
# signal that kills the wrapper too (SIGKILL preemption) leaves no file,
# which adoption classifies as a signal death (137, retryable).
_EXIT_CAPTURE_SH = (
    'ef="$1"; shift; "$@"; rc=$?; '
    'printf %s "$rc" > "$ef.tmp" && mv -f "$ef.tmp" "$ef"; exit "$rc"'
)


def replica_slots(template: ProcessTemplate) -> int:
    """Scheduling weight of one replica in device slots (reference: pods
    request resource QUANTITIES — ``google.com/tpu: N`` — and the
    scheduler sums them; a replica asking for 4 chips occupies 4 slots of
    ``--max-slots`` capacity). Minimum 1: even a device-less control
    process occupies a scheduling slot."""
    r = template.resources
    return max(1, r.tpu_chips, r.cpu_devices)


def normalize_exit_code(code: Optional[int]) -> Optional[int]:
    """Map Popen's signal encoding (-N) to the container convention (128+N)
    the ExitCode restart policy is defined against — so SIGKILL surfaces as
    137 (retryable), matching the reference's pod-level semantics."""
    if code is not None and code < 0:
        return 128 - code
    return code


@dataclass
class ReplicaHandle:
    """Tracking record for one replica process (pod-object analog)."""

    name: str
    job_key: str
    replica_type: ReplicaType
    index: int
    phase: ReplicaPhase = ReplicaPhase.PENDING
    exit_code: Optional[int] = None
    pid: Optional[int] = None
    created_at: float = 0.0
    finished_at: Optional[float] = None
    log_path: Optional[str] = None
    slots: int = 1  # device-slot weight (replica_slots of the template)

    def is_active(self) -> bool:
        return self.phase in (ReplicaPhase.PENDING, ReplicaPhase.RUNNING)

    def is_finished(self) -> bool:
        return self.phase in (ReplicaPhase.SUCCEEDED, ReplicaPhase.FAILED)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "job_key": self.job_key,
            "replica_type": self.replica_type.value,
            "index": self.index,
            "phase": self.phase.value,
            "exit_code": self.exit_code,
            "pid": self.pid,
            "created_at": self.created_at,
            "finished_at": self.finished_at,
            "log_path": self.log_path,
            "slots": self.slots,
        }


class ProcessRunner:
    """Interface both runners implement."""

    def create(
        self,
        job_key: str,
        rtype: ReplicaType,
        index: int,
        template: ProcessTemplate,
        env: Dict[str, str],
    ) -> ReplicaHandle:
        raise NotImplementedError

    def delete(self, name: str, grace_seconds: float = 5.0) -> None:
        raise NotImplementedError

    def delete_many(self, names: List[str], grace_seconds: float = 5.0) -> None:
        """Tear down several replicas; runners with a real kill-escalation
        wait override this to share one escalation across the batch."""
        for name in names:
            self.delete(name, grace_seconds)

    def sync(self) -> None:
        """Poll live processes and update phases (informer-refresh analog)."""

    def list_for_job(self, job_key: str) -> List[ReplicaHandle]:
        raise NotImplementedError

    def get(self, name: str) -> Optional[ReplicaHandle]:
        raise NotImplementedError

    def remove_record(self, name: str) -> None:
        """Forget a finished replica's record (pod object deletion analog)."""
        raise NotImplementedError

    def schedulable_slots(self) -> Optional[int]:
        """Free scheduling slots, or None for unlimited (gang admission input)."""
        return None

    def rescan(self, key_filter=None) -> None:
        """Adopt state left by another incarnation (hot-standby takeover);
        no-op for runners without persistence. ``key_filter`` (job key →
        bool) limits adoption to owned jobs — a SHARDED supervisor must
        not start tracking (and counting against its capacity) replicas
        another shard owner reconciles."""

    def take_changed_keys(self) -> Optional[set]:
        """Job keys whose replica set changed (create/delete/phase
        transition/kill) since the last call, consumed. Returns None
        when this runner does not track changes — callers must then
        assume EVERYTHING changed (disables the supervisor's steady
        fast path, never its correctness)."""
        return None

    def forget_job(self, job_key: str) -> None:
        """Drop in-memory tracking of a job's replicas WITHOUT touching
        the processes or their persisted records — the shard hand-off
        primitive: the releasing supervisor forgets, the new owner
        adopts via ``rescan``."""

    def capacity_slots(self) -> Optional[int]:
        """Total device-slot capacity, or None for unbounded."""
        return None

    def list_all(self) -> List[ReplicaHandle]:
        """Every tracked replica handle (all jobs)."""
        raise NotImplementedError

    def set_slots(self, name: str, slots: int) -> None:
        """Correct a replica's device-slot weight (template is the source
        of truth; records from pre-weight supervisors need healing)."""
        h = self.get(name)
        if h is not None:
            h.slots = slots

    def inject_kill(self, name: str) -> None:
        """Fault-injection site (faults/): make this replica die as if
        the host preempted it — an abrupt SIGKILL-style death, NOT a
        graceful delete (the record survives so the reconciler walks the
        real failure-classification path: exit 137, retryable)."""

    def inject_preempt(self, name: str) -> None:
        """Fault-injection site (faults/ ``preempt_replica``): a
        SIGTERM-with-grace death, distinct from :meth:`inject_kill`'s
        abrupt SIGKILL — models a managed eviction (exit 143, retryable).
        Runners without real signals fall back to kill semantics."""
        self.inject_kill(name)

    def standby_ready(self) -> int:
        """Warm standby processes ready for promotion (hot spares);
        0 for runners without a pool."""
        return 0

    def set_standby_target(self, n: int) -> None:
        """Size the warm-standby pool (lazily created on first nonzero
        target); no-op for runners without one."""


class FakeRunner(ProcessRunner):
    """In-memory runner for controller tests (fake clientset analog).

    Created replicas start PENDING; tests move them with :meth:`set_phase`.
    Every create/delete is appended to :attr:`actions` for assertions, and
    the env each replica was created with is kept in :attr:`envs`.
    """

    def __init__(self, capacity: Optional[int] = None):
        self.handles: Dict[str, ReplicaHandle] = {}
        # Warm-standby model for hot-spare tests: a plain counter (set
        # directly or via set_standby_target) that standby_ready returns.
        self.standby = 0
        # Per-job handle index: list_for_job is the reconciler's hottest
        # read (every sync of every job), and a flat scan of ALL handles
        # made a pass O(jobs x replicas) in pure bookkeeping.
        self._by_job: Dict[str, Dict[str, ReplicaHandle]] = {}
        self.envs: Dict[str, Dict[str, str]] = {}
        self.templates: Dict[str, ProcessTemplate] = {}
        self.actions: List[tuple] = []
        self.capacity = capacity  # None = unlimited
        # Same thread-safety contract as SubprocessRunner: per-key reconcile
        # locks serialize same-key access, but different keys hit the shared
        # dicts concurrently (tests/test_stress.py).
        self._lock = threading.RLock()
        # Job keys with replica-set changes since the last drain — feeds
        # the supervisor's steady fast path.
        self._changed_keys: set = set()

    def create(self, job_key, rtype, index, template, env):
        from .. import faults

        name = replica_name(job_key, rtype, index)
        with self._lock:
            if name in self.handles:
                raise RuntimeError(f"duplicate create for {name}")
            env = faults.thread_env(dict(env))
            inj = faults.active()
            if inj is not None and inj.spawn_should_fail(rtype.value, index):
                h = ReplicaHandle(
                    name=name,
                    job_key=job_key,
                    replica_type=rtype,
                    index=index,
                    phase=ReplicaPhase.FAILED,
                    exit_code=128 + 9,  # launch casualty: retryable
                    created_at=time.time(),
                    finished_at=time.time(),
                    slots=replica_slots(template),
                )
            else:
                h = ReplicaHandle(
                    name=name,
                    job_key=job_key,
                    replica_type=rtype,
                    index=index,
                    phase=ReplicaPhase.PENDING,
                    created_at=time.time(),
                    slots=replica_slots(template),
                )
            self.handles[name] = h
            self._by_job.setdefault(job_key, {})[name] = h
            self.envs[name] = dict(env)
            self.templates[name] = template
            self.actions.append(("create", name))
            self._changed_keys.add(job_key)
            return h

    def _index_pop(self, name: str) -> Optional[ReplicaHandle]:
        h = self.handles.pop(name, None)
        if h is not None:
            per_job = self._by_job.get(h.job_key)
            if per_job is not None:
                per_job.pop(name, None)
                if not per_job:
                    self._by_job.pop(h.job_key, None)
        return h

    def delete(self, name, grace_seconds: float = 5.0):
        with self._lock:
            self.actions.append(("delete", name))
            h = self._index_pop(name)
            if h is not None:
                self.envs.pop(name, None)
                self.templates.pop(name, None)
                self._changed_keys.add(h.job_key)

    def sync(self):
        pass

    def take_changed_keys(self):
        with self._lock:
            out, self._changed_keys = self._changed_keys, set()
            return out

    def forget_job(self, job_key):
        with self._lock:
            for name in list(self._by_job.get(job_key, {})):
                self._index_pop(name)
                self.envs.pop(name, None)
                self.templates.pop(name, None)

    def list_for_job(self, job_key):
        with self._lock:
            return list(self._by_job.get(job_key, {}).values())

    def get(self, name):
        with self._lock:
            return self.handles.get(name)

    def remove_record(self, name):
        with self._lock:
            h = self._index_pop(name)
            if h is not None:
                self._changed_keys.add(h.job_key)

    def schedulable_slots(self):
        with self._lock:
            if self.capacity is None:
                return None
            used = sum(h.slots for h in self.handles.values() if h.is_active())
            return max(0, self.capacity - used)

    def capacity_slots(self):
        return self.capacity

    def list_all(self):
        with self._lock:
            return list(self.handles.values())

    def inject_kill(self, name: str) -> None:
        with self._lock:
            h = self.handles.get(name)
            if h is not None and h.is_active():
                h.phase = ReplicaPhase.FAILED
                h.exit_code = 137  # signal death, retryable
                h.finished_at = time.time()
                self._changed_keys.add(h.job_key)

    def inject_preempt(self, name: str) -> None:
        with self._lock:
            h = self.handles.get(name)
            if h is not None and h.is_active():
                h.phase = ReplicaPhase.FAILED
                h.exit_code = 143  # SIGTERM death, retryable
                h.finished_at = time.time()
                self._changed_keys.add(h.job_key)

    def standby_ready(self) -> int:
        return self.standby

    def set_standby_target(self, n: int) -> None:
        # Tests model the pool as an instantly-warm counter.
        self.standby = max(0, int(n))

    # --- test helpers ---

    def set_phase(self, name: str, phase: ReplicaPhase, exit_code: Optional[int] = None):
        with self._lock:
            h = self.handles[name]
            h.phase = phase
            if exit_code is not None:
                h.exit_code = exit_code
            if phase in (ReplicaPhase.SUCCEEDED, ReplicaPhase.FAILED):
                h.finished_at = time.time()
            self._changed_keys.add(h.job_key)

    def set_all_running(self, job_key: str):
        with self._lock:
            for h in self.list_for_job(job_key):
                if h.phase == ReplicaPhase.PENDING:
                    h.phase = ReplicaPhase.RUNNING
                    self._changed_keys.add(job_key)


class SubprocessRunner(ProcessRunner):
    """Real runner: replicas are local OS processes.

    stdout+stderr of each replica goes to
    ``<state_dir>/logs/<ns>_<job>-<type>-<index>.log`` (kubectl-logs analog).
    ``max_slots`` bounds concurrently active DEVICE SLOTS — the "cluster
    capacity" gang admission checks against; each replica occupies
    ``replica_slots(template)`` of it (a 4-chip replica weighs 4).
    """

    def __init__(
        self,
        state_dir: Path,
        max_slots: Optional[int] = None,
        standby: int = 0,
    ):
        self.state_dir = Path(state_dir)
        self.log_dir = self.state_dir / "logs"
        self.log_dir.mkdir(parents=True, exist_ok=True)
        # Replica records persist here so a restarted supervisor re-adopts
        # live replicas instead of double-creating the world (reference:
        # pods live in the API server; a controller restart lists + claims
        # them, SURVEY.md §3.2 "label-claim + adoption").
        self.replica_dir = self.state_dir / "replicas"
        self.replica_dir.mkdir(parents=True, exist_ok=True)
        self.max_slots = max_slots
        # Pre-warmed standby processes (controller/standby.py): create()
        # hands module-template jobs to one instead of spawning cold,
        # cutting schedule-to-first-step by the interpreter+import tax.
        self._standby_pool = None
        if standby > 0:
            from .standby import StandbyPool

            self._standby_pool = StandbyPool(self.state_dir, standby)
            self._standby_pool.replenish()
        self.handles: Dict[str, ReplicaHandle] = {}
        # Per-job handle index (see FakeRunner._by_job): keeps
        # list_for_job O(own replicas) instead of O(all replicas).
        self._by_job: Dict[str, Dict[str, ReplicaHandle]] = {}
        self._procs: Dict[str, subprocess.Popen] = {}
        self._log_files: Dict[str, object] = {}
        # Replicas adopted from a previous incarnation: polled via /proc
        # (they are not our children, so no Popen/waitpid).
        self._adopted: Dict[str, int] = {}  # name -> pid
        self._pid_starts: Dict[str, Optional[int]] = {}
        # Standby-run replicas have NO sh wrapper: the handle's pid IS the
        # workload, so "wrapper dead but group alive" does NOT mean the
        # replica survives — liveness for these is pid-only (persisted in
        # the record for adoption across supervisor restarts).
        self._wrapperless: set = set()
        # Job keys with replica-set changes since the last drain (steady
        # fast path), and reaped-but-untracked Popen objects left by
        # forget_job (a disowned child must still be wait()ed or it
        # lingers as a zombie until this process exits).
        self._changed_keys: set = set()
        self._disowned: List[subprocess.Popen] = []
        self._lock = threading.RLock()
        self._load_records()

    # ---- persistence + adoption ----

    def _record_path(self, name: str) -> Path:
        return self.replica_dir / (key_to_fs(name) + ".json")

    def _exit_path(self, name: str) -> Path:
        return self.replica_dir / (key_to_fs(name) + ".exit")

    def _save(self, h: ReplicaHandle, only_if_tracked: bool = False) -> None:
        """``only_if_tracked``: phase-update saves must not resurrect a
        record another incarnation's delete() just unlinked (shared state
        dir) — a stale FAILED record would be adopted by the next start."""
        if only_if_tracked and not self._record_path(h.name).exists():
            return
        rec = h.to_dict()
        rec["pid_start"] = self._pid_starts.get(h.name)
        rec["wrapperless"] = h.name in self._wrapperless
        tmp = self._record_path(h.name).with_suffix(".json.tmp")
        tmp.write_text(json.dumps(rec))
        tmp.replace(self._record_path(h.name))

    def _forget_files(self, name: str) -> None:
        for p in (self._record_path(name), self._exit_path(name)):
            try:
                p.unlink()
            except OSError:
                pass

    def _read_exit_file(self, name: str) -> Optional[int]:
        try:
            return int(self._exit_path(name).read_text().strip())
        except (OSError, ValueError):
            return None

    def _index_add(self, h: ReplicaHandle) -> None:
        self.handles[h.name] = h
        self._by_job.setdefault(h.job_key, {})[h.name] = h
        self._changed_keys.add(h.job_key)

    def _index_pop(self, name: str) -> Optional[ReplicaHandle]:
        h = self.handles.pop(name, None)
        if h is not None:
            per_job = self._by_job.get(h.job_key)
            if per_job is not None:
                per_job.pop(name, None)
                if not per_job:
                    self._by_job.pop(h.job_key, None)
            self._changed_keys.add(h.job_key)
        return h

    def rescan(self, key_filter=None) -> None:
        """Adopt the worlds another incarnation left behind — the
        hot-standby takeover step. The standby's startup snapshot (taken
        while the old leader was still mutating records) is DISCARDED for
        every replica that is not this runner's own live child: the disk
        records the dead leader wrote are strictly fresher (it may have
        restarted replicas under new pids since we loaded). Own children
        (``self._procs``) keep their live Popen state. ``key_filter``
        (sharded takeover) adopts only owned jobs' records."""
        with self._lock:
            for name in list(self.handles):
                if name not in self._procs:
                    self._index_pop(name)
                    self._adopted.pop(name, None)
                    self._pid_starts.pop(name, None)
            self._load_records(
                persist_classification=True, key_filter=key_filter
            )

    def take_changed_keys(self):
        with self._lock:
            out, self._changed_keys = self._changed_keys, set()
            return out

    def forget_job(self, job_key):
        """Shard hand-off: stop tracking this job's replicas. Processes
        and persisted records are untouched (the new owner adopts both);
        our OWN live children move to a reap list so they cannot
        zombify if they exit before this process does."""
        with self._lock:
            for name in list(self._by_job.get(job_key, {})):
                self._index_pop(name)
                proc = self._procs.pop(name, None)
                if proc is not None:
                    self._disowned.append(proc)
                f = self._log_files.pop(name, None)
                if f is not None:
                    f.close()
                self._adopted.pop(name, None)
                self._pid_starts.pop(name, None)
                self._wrapperless.discard(name)

    def _load_records(
        self, persist_classification: bool = False, key_filter=None
    ) -> None:
        """Adopt persisted replicas: live pids (same /proc start time) come
        back RUNNING; dead ones get their exit code from the exit-capture
        file, or 137 (signal death, retryable) if none was written.

        Already-tracked names are skipped (this runner's live knowledge
        wins over its own earlier records). ``persist_classification`` is
        False at construction: a daemon may be a mere STANDBY whose leader
        still owns these records — classifying dead replicas must not
        write state to disk until this incarnation holds the lease
        (rescan) or actively reconciles (sync)."""
        for rec_file in sorted(self.replica_dir.glob("*.json")):
            try:
                rec = json.loads(rec_file.read_text())
                if rec.get("name") in self.handles:
                    continue
                if key_filter is not None and not key_filter(
                    rec.get("job_key", "")
                ):
                    continue
                h = ReplicaHandle(
                    name=rec["name"],
                    job_key=rec["job_key"],
                    replica_type=ReplicaType(rec["replica_type"]),
                    index=rec["index"],
                    phase=ReplicaPhase(rec["phase"]),
                    exit_code=rec.get("exit_code"),
                    pid=rec.get("pid"),
                    created_at=rec.get("created_at", 0.0),
                    finished_at=rec.get("finished_at"),
                    log_path=rec.get("log_path"),
                    slots=int(rec.get("slots", 1)),
                )
            except Exception as e:
                # A corrupt/foreign-schema record must not brick every
                # supervisor start; quarantine it — loudly, so an
                # operator learns replicas went untracked — and move on.
                print(
                    f"[tpujob] quarantining corrupt replica record "
                    f"{rec_file.name}: {e}",
                    file=sys.stderr,
                )
                try:
                    rec_file.replace(rec_file.with_suffix(".json.corrupt"))
                except OSError:
                    pass  # invariant: waived — quarantine rename is best-effort; the parse failure was already reported
                continue
            pid_start = rec.get("pid_start")
            self._pid_starts[h.name] = pid_start
            if rec.get("wrapperless"):
                self._wrapperless.add(h.name)
            if h.is_active():
                # Exit-capture file first: the wrapper writes it when the
                # replica's MAIN process exits, so its presence means done
                # even if a stray background child keeps the group alive.
                alive = (
                    _pid_alive(h.pid, pid_start)
                    if h.name in self._wrapperless
                    else _replica_alive(h.pid, pid_start)
                )
                if self._read_exit_file(h.name) is not None:
                    self._finish_dead_adopted(h, save=persist_classification)
                elif alive:
                    h.phase = ReplicaPhase.RUNNING
                    self._adopted[h.name] = h.pid
                else:
                    self._finish_dead_adopted(h, save=persist_classification)
            self._index_add(h)

    def _finish_dead_adopted(self, h: ReplicaHandle, save: bool = True) -> None:
        """Classify a replica found dead without a waitpid: exit-capture file
        if written, else 137 (group signal killed the wrapper too —
        the preemption case, retryable under ExitCode policy).
        ``save=False`` keeps the classification in memory only (a standby
        must not write records another incarnation owns)."""
        code = self._read_exit_file(h.name)
        h.exit_code = 137 if code is None else code
        h.phase = (
            ReplicaPhase.SUCCEEDED if h.exit_code == 0 else ReplicaPhase.FAILED
        )
        h.finished_at = time.time()
        self._changed_keys.add(h.job_key)
        if save:
            self._save(h, only_if_tracked=True)

    def _argv(self, template: ProcessTemplate, exit_path: Path) -> List[str]:
        if template.command:
            argv = list(template.command)
        else:
            argv = [sys.executable, "-m", template.module]
        argv += list(template.args)
        return ["/bin/sh", "-c", _EXIT_CAPTURE_SH, "sh", str(exit_path)] + argv

    def create(self, job_key, rtype, index, template, env):
        from .. import faults

        name = replica_name(job_key, rtype, index)
        with self._lock:
            if name in self.handles and self.handles[name].is_active():
                raise RuntimeError(f"duplicate create for live replica {name}")
            log_path = self.log_dir / (key_to_fs(name) + ".log")
            full_env = dict(os.environ)
            full_env.update(template.env)
            full_env.update(env)
            # Chaos threading: an armed fault plan rides into the replica
            # (worker-side faults fire inside the subprocess itself).
            faults.thread_env(full_env)
            # Replicas must import this package regardless of cwd, and the
            # inherited PYTHONPATH is preserved (the user's own modules).
            pkg_root = str(Path(__file__).resolve().parents[2])
            parts = [p for p in full_env.get("PYTHONPATH", "").split(os.pathsep) if p]
            if pkg_root not in parts:
                parts.insert(0, pkg_root)
            full_env["PYTHONPATH"] = os.pathsep.join(parts)
            self._forget_files(name)  # stale record/exit file of a prior run
        # Pre-warmed path: hand the job to a ready standby (module
        # templates only — exec'ing a command argv would discard the warm
        # imports). OUTSIDE the handle lock: assign() can block up to its
        # ack timeout when a standby dies mid-handoff, and sync/delete/
        # list must not freeze for that. Per-key reconcile serialization
        # already prevents same-name concurrent creates; the handle is
        # installed under the lock below. Ack failure falls through to
        # the cold spawn.
        if self._standby_pool is not None and template.module:
            taken = self._standby_pool.take()
            if taken is not None:
                sid, proc = taken
                ok = self._standby_pool.assign(
                    sid,
                    proc,
                    {
                        "module": template.module,
                        "args": list(template.args),
                        "env": full_env,
                        "cwd": template.working_dir or None,
                        "log_path": str(log_path),
                        "exit_path": str(self._exit_path(name)),
                    },
                )
                if ok:
                    with self._lock:
                        h = ReplicaHandle(
                            name=name,
                            job_key=job_key,
                            replica_type=rtype,
                            index=index,
                            phase=ReplicaPhase.RUNNING,
                            pid=proc.pid,
                            created_at=time.time(),
                            log_path=str(log_path),
                            slots=replica_slots(template),
                        )
                        self._index_add(h)
                        self._procs[name] = proc
                        stat = _proc_stat(proc.pid)
                        self._pid_starts[name] = stat[0] if stat else None
                        self._wrapperless.add(name)
                        self._save(h)
                        return h
        with self._lock:
            log_f = open(log_path, "ab")
            try:
                inj = faults.active()
                if inj is not None and inj.spawn_should_fail(
                    rtype.value, index
                ):
                    raise OSError("injected spawn failure (fault plan)")
                proc = subprocess.Popen(
                    self._argv(template, self._exit_path(name)),
                    env=full_env,
                    cwd=template.working_dir or None,
                    stdout=log_f,
                    stderr=subprocess.STDOUT,
                    start_new_session=True,  # isolate signals from supervisor
                )
            except OSError as e:
                log_f.write(f"[tpujob] failed to launch: {e}\n".encode())
                log_f.close()
                h = ReplicaHandle(
                    name=name,
                    job_key=job_key,
                    replica_type=rtype,
                    index=index,
                    phase=ReplicaPhase.FAILED,
                    exit_code=127,
                    created_at=time.time(),
                    finished_at=time.time(),
                    log_path=str(log_path),
                    slots=replica_slots(template),
                )
                self._index_add(h)
                self._save(h)
                return h
            h = ReplicaHandle(
                name=name,
                job_key=job_key,
                replica_type=rtype,
                index=index,
                phase=ReplicaPhase.RUNNING,
                pid=proc.pid,
                created_at=time.time(),
                log_path=str(log_path),
                slots=replica_slots(template),
            )
            self._index_add(h)
            self._procs[name] = proc
            self._log_files[name] = log_f
            stat = _proc_stat(proc.pid)
            self._pid_starts[name] = stat[0] if stat else None
            self._save(h)
            return h

    def sync(self):
        if self._standby_pool is not None:
            # Outside the handle lock: replenish spawns processes.
            self._standby_pool.replenish()
        with self._lock:
            # Reap children disowned by a shard hand-off (forget_job):
            # still our OS children until they exit, never our replicas.
            if self._disowned:
                self._disowned = [
                    p for p in self._disowned if p.poll() is None
                ]
            for name, proc in list(self._procs.items()):
                code = proc.poll()
                if code is None:
                    continue
                self._procs.pop(name)
                f = self._log_files.pop(name, None)
                if f is not None:
                    f.close()
                h = self.handles[name]
                file_code = self._read_exit_file(name)
                if (
                    code < 0
                    and file_code is None
                    and name not in self._wrapperless
                    and _group_members_alive(proc.pid)
                ):
                    # The wrapper was killed by a signal but the replica's
                    # group survives (TERM-trapping replica, stray kill of
                    # the sh): the replica is NOT dead — demote to
                    # adopted-style group tracking. (A wrapper that EXITS
                    # has waited for its child, so exit ⇒ replica done; an
                    # exit file means the main child finished first.)
                    self._adopted[name] = proc.pid
                    continue
                h.exit_code = (
                    file_code if file_code is not None else normalize_exit_code(code)
                )
                h.phase = (
                    ReplicaPhase.SUCCEEDED
                    if h.exit_code == 0
                    else ReplicaPhase.FAILED
                )
                h.finished_at = time.time()
                self._changed_keys.add(h.job_key)
                self._save(h, only_if_tracked=True)
            # Adopted replicas (previous incarnation's children): when the
            # exit-capture file exists the replica's main process is done
            # (stray group survivors don't keep it RUNNING); otherwise poll
            # /proc — one pass amortized over all adopted names. A dead
            # group with no exit file means a group signal killed the
            # wrapper too (preemption) → 137.
            live_pgids = _live_pgids() if self._adopted else None
            for name, pid in list(self._adopted.items()):
                alive = (
                    _pid_alive(pid, self._pid_starts.get(name))
                    if name in self._wrapperless
                    else _replica_alive(pid, self._pid_starts.get(name), live_pgids)
                )
                if self._read_exit_file(name) is None and alive:
                    continue
                self._adopted.pop(name)
                self._finish_dead_adopted(self.handles[name])

    def inject_kill(self, name: str) -> None:
        """Abrupt group SIGKILL — the preemption model. The handle and
        exit-capture file stay untouched: sync() finds the group dead
        with no exit file and classifies 137 (retryable), exactly like a
        real host preemption."""
        with self._lock:
            h = self.handles.get(name)
            pid = h.pid if h is not None else None
        if pid is None:
            return
        start = self._pid_starts.get(name)
        stat = _proc_stat(pid)
        if stat is not None and start is not None and stat[0] != start:
            return  # pid reused by a stranger — never signal it
        try:
            os.killpg(pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass

    def inject_preempt(self, name: str) -> None:
        """Graceful preemption — group SIGTERM, no escalation wait (the
        sync pass must not block on a TERM-trapping replica). A default
        handler dies with 143 (retryable ≥128); the reconciler walks the
        same failure-classification path as a real managed eviction."""
        with self._lock:
            h = self.handles.get(name)
            pid = h.pid if h is not None else None
        if pid is None:
            return
        start = self._pid_starts.get(name)
        stat = _proc_stat(pid)
        if stat is not None and start is not None and stat[0] != start:
            return  # pid reused by a stranger — never signal it
        try:
            os.killpg(pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            pass

    def standby_ready(self) -> int:
        with self._lock:
            pool = self._standby_pool
        return pool.ready_count() if pool is not None else 0

    def set_standby_target(self, n: int) -> None:
        """Grow/shrink the warm pool; lazily creates it when hot spares
        first demand one (constructor ``standby=0`` stays the default)."""
        n = max(0, int(n))
        with self._lock:
            pool = self._standby_pool
            if pool is None:
                if n <= 0:
                    return
                from .standby import StandbyPool

                pool = StandbyPool(self.state_dir, n)
                self._standby_pool = pool
            else:
                pool.set_size(n)
        pool.replenish()

    def delete(self, name, grace_seconds: float = 5.0):
        self.delete_many([name], grace_seconds)

    def delete_many(self, names, grace_seconds: float = 5.0):
        """Tear down a batch of replicas with ONE shared TERM→KILL
        escalation: every group is signaled up front, then a single
        /proc-scan loop waits for all of them together. A TERM-trapping
        multi-replica world therefore costs ~grace+2s for the whole batch,
        not per replica — the reconcile loop (which calls this serially
        for suspends/preemptions) must not stall for minutes while other
        jobs wait to be synced."""
        pending = []  # (name, handle, pgid, wrapper Popen or None)
        # One /proc snapshot covers the whole signaling phase (groups only
        # lose members, so a group empty here stays empty); the wait loop
        # below re-scans fresh each tick.
        live_pgids = _live_pgids() if names else set()
        for name in names:
            with self._lock:
                proc = self._procs.get(name)
                h = self.handles.get(name)
                adopted_pid = self._adopted.get(name)
            if proc is not None:
                if proc.poll() is None or proc.pid in live_pgids:
                    # SIGTERM the whole group. proc is the exit-capture
                    # wrapper, which dies on TERM even when the replica
                    # traps it; if the wrapper pre-deceased the replica
                    # (stray kill, OOM) the survivors still get the
                    # graceful signal before the shared escalation.
                    try:
                        os.killpg(proc.pid, signal.SIGTERM)
                    except (ProcessLookupError, PermissionError):
                        pass
                pending.append((name, h, proc.pid, proc))
            elif adopted_pid is not None:
                # Adopted replica: not our child — poll /proc for
                # termination instead of waitpid, same TERM→KILL path.
                if self._term_group(name, adopted_pid, live_pgids):
                    pending.append((name, h, adopted_pid, None))
            elif h is not None and h.pid is not None:
                # Neither our child nor adopted-live: a replica already
                # classified finished. Its wrapper is gone, but a TERM-
                # trapping descendant may survive — reap group members.
                if self._term_group(name, h.pid, live_pgids):
                    pending.append((name, h, h.pid, None))
        self._ensure_groups_dead([p[2] for p in pending], grace_seconds)
        for name, h, pgid, proc in pending:
            if proc is not None:
                # Group is dead (or just SIGKILLed), so the wrapper is at
                # worst a zombie — reap it.
                proc.wait()
        for name in names:
            with self._lock:
                h = self.handles.get(name)
                proc = self._procs.pop(name, None)
                if proc is not None and h is not None:
                    h.exit_code = normalize_exit_code(proc.returncode)
                    h.phase = (
                        ReplicaPhase.FAILED
                        if proc.returncode
                        else ReplicaPhase.SUCCEEDED
                    )
                    h.finished_at = time.time()
                f = self._log_files.pop(name, None)
                if f is not None:
                    f.close()
                self._adopted.pop(name, None)
                self._pid_starts.pop(name, None)
                self.handles.pop(name, None)
                self._forget_files(name)

    def _term_group(self, name: str, pid: int, live_pgids=None) -> bool:
        """SIGTERM a replica's process group we hold no Popen for — adopted
        replicas AND group survivors of already-finished wrappers (the name
        is the group id; pid-reuse strangers are never signaled). Returns
        whether a signal was sent (i.e. the group needs a death-wait).
        ``live_pgids`` lets a batch caller amortize the /proc pass."""
        members_alive = (
            pid in live_pgids if live_pgids is not None else _group_members_alive(pid)
        )
        start = self._pid_starts.get(name)
        stat = _proc_stat(pid)
        if (
            stat is not None
            and stat[1] != "Z"
            and start is not None
            and stat[0] != start
        ):
            return False  # pid reused by a stranger — never signal it
        if not _pid_alive(pid, start) and not members_alive:
            # Wrapper gone and no surviving group members (a pid stays
            # allocated while it is a live pgid, so members ⇒ ours).
            return False
        try:
            os.killpg(pid, signal.SIGTERM)
        except (ProcessLookupError, PermissionError):
            return False
        return True

    def _ensure_groups_dead(self, pgids, grace_seconds: float) -> None:
        """Wait until every member of every listed process group has
        exited, escalating to group SIGKILLs when the grace budget runs
        out. One /proc scan per tick covers the whole batch."""
        waiting = set(pgids)
        if not waiting:
            return
        # monotonic: a clock step during teardown must not skip the
        # grace period (SIGKILL lands on a checkpoint-flushing child) or
        # extend it indefinitely.
        deadline = time.monotonic() + grace_seconds
        while waiting and time.monotonic() < deadline:
            waiting &= _live_pgids()
            if not waiting:
                return
            time.sleep(0.05)
        for pgid in list(waiting):
            try:
                os.killpg(pgid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                waiting.discard(pgid)
        kill_deadline = time.monotonic() + 2.0
        while waiting and time.monotonic() < kill_deadline:
            waiting &= _live_pgids()
            time.sleep(0.05)

    def list_for_job(self, job_key):
        with self._lock:
            return [h for h in self.handles.values() if h.job_key == job_key]

    def get(self, name):
        with self._lock:
            return self.handles.get(name)

    def remove_record(self, name):
        with self._lock:
            if name in self._procs or name in self._adopted:
                raise RuntimeError(f"cannot remove record of live replica {name}")
            self.handles.pop(name, None)
            self._pid_starts.pop(name, None)
            self._wrapperless.discard(name)
            self._forget_files(name)

    def set_slots(self, name, slots):
        """Heal a stale weight AND persist it — an in-memory-only heal
        would re-open the overcommit window on every supervisor restart."""
        with self._lock:
            h = self.handles.get(name)
            if h is not None and h.slots != slots:
                h.slots = slots
                self._save(h, only_if_tracked=True)

    def schedulable_slots(self):
        if self.max_slots is None:
            return None
        with self._lock:
            used = sum(h.slots for h in self.handles.values() if h.is_active())
        return max(0, self.max_slots - used)

    def capacity_slots(self):
        return self.max_slots

    def list_all(self):
        with self._lock:
            return list(self.handles.values())

    def shutdown(self):
        """Terminate replicas THIS incarnation spawned (supervisor exit).

        Adopted replicas are spared: they are another incarnation's world
        (possibly a live daemon sharing the state dir with a foreground
        ``tpujob run``), and the reference's controller shutdown never kills
        pods it merely adopted — job-scoped ``delete()`` remains the only
        path that tears them down.
        """
        with self._lock:
            names = list(self._procs.keys())
        self.delete_many(names, grace_seconds=2.0)
        if self._standby_pool is not None:
            self._standby_pool.shutdown()  # idle standbys die with us
