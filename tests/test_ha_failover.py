"""HA failover end-to-end: leader daemon dies, the hot standby acquires
the lease and ADOPTS the live world — no duplicate replicas, no lost job.

This composes the two restart-safety mechanisms that are otherwise tested
separately: the flock leader lease (released by the kernel on holder
death, tests/test_monitoring.py) and replica adoption from persisted
records (tests/test_adoption.py). The reference gets the same property
from k8s leader election + pods living in the API server.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

# Fast-lane exclusion (-m 'not slow'): real-subprocess HA leader failover.
pytestmark = pytest.mark.slow

REPO_ROOT = str(Path(__file__).resolve().parents[1])


def spawn_daemon(state_dir, log_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "pytorch_operator_tpu.client.cli",
            "--state-dir",
            str(state_dir),
            "supervisor",
            "--interval",
            "0.2",
        ],
        env=env,
        stdout=open(log_path, "ab"),
        stderr=subprocess.STDOUT,
    )


def wait_for(cond, timeout, interval=0.2):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


def job_state(state_dir, key):
    p = state_dir / "jobs" / (key.replace("/", "_") + ".json")
    if not p.exists():
        return None
    try:
        return json.loads(p.read_text())
    except ValueError:
        return None


def test_leader_crash_standby_adopts_world(tmp_path):
    state = tmp_path / "state"
    state.mkdir()
    # Leader + hot standby share the state dir; the standby blocks on the
    # lease until the leader dies.
    d1 = spawn_daemon(state, tmp_path / "d1.log")
    d2 = spawn_daemon(state, tmp_path / "d2.log")

    def log(name):
        p = tmp_path / name
        return p.read_text() if p.exists() else ""

    # flock acquisition order is NOT spawn order: identify the actual
    # holder before killing, or the test can pass without ever exercising
    # failover (killing the standby proves nothing).
    assert wait_for(
        lambda: ("standby —" in log("d1.log")) != ("standby —" in log("d2.log")),
        30,
    ), "could not identify a unique standby from the daemon logs"
    if "standby —" in log("d1.log"):
        standby, leader = d1, d2
    else:
        standby, leader = d2, d1
    try:
        # Submit a job whose master sleeps long enough to straddle failover.
        spec = {
            "api_version": "tpujob.dev/v1",
            "kind": "TPUJob",
            "metadata": {"name": "ha"},
            "spec": {
                "replica_specs": {
                    "Master": {
                        "replicas": 1,
                        "template": {
                            "command": ["sh", "-c", "sleep 12; echo ha-done"]
                        },
                    }
                }
            },
        }
        from pytorch_operator_tpu.api import job_from_dict
        from pytorch_operator_tpu.controller.store import JobStore

        store = JobStore(persist_dir=state / "jobs")
        key = store.add(job_from_dict(spec))

        # The (single) active daemon launches the replica.
        rec_dir = state / "replicas"
        assert wait_for(
            lambda: rec_dir.is_dir() and list(rec_dir.glob("*.json")), 30
        ), "leader never launched the replica"
        rec_file = next(rec_dir.glob("*.json"))
        pid_before = json.loads(rec_file.read_text())["pid"]

        # Kill the leader without cleanup: the replica must survive and the
        # standby must take over.
        os.kill(leader.pid, signal.SIGKILL)
        leader.wait(timeout=10)

        def succeeded():
            rec = job_state(state, key)
            if rec is None:
                return False
            return any(
                c.get("type") == "Succeeded" and c.get("status")
                for c in rec.get("status", {}).get("conditions", [])
            )

        assert wait_for(succeeded, 60), "standby never completed the job"

        # One creation only — the standby ADOPTED pid_before, it did not
        # double-create the world.
        from pytorch_operator_tpu.controller.events import load_merged_events

        creates = [
            rec
            for rec in load_merged_events(state / "events" / "default_ha.events.jsonl")
            if rec["reason"] == "SuccessfulCreateReplica"
        ]
        # One creation, once: the aggregation write-through would surface
        # a double-create as count>1 even within one merged record.
        assert len(creates) == 1 and int(creates[0].get("count", 1)) == 1, creates
        # And the log shows exactly one run of the workload.
        log = (state / "logs" / "default_ha-master-0.log").read_text()
        assert log.count("ha-done") == 1
        assert pid_before is not None
    finally:
        for proc in (d1, d2):
            if proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()


def test_rescan_replaces_stale_standby_snapshot(tmp_path):
    """A standby that adopted a replica at ITS startup must, at takeover,
    prefer the disk record (the leader may have restarted the replica
    under a new pid while the standby waited)."""
    from pytorch_operator_tpu.api.types import ProcessTemplate, ReplicaType
    from pytorch_operator_tpu.controller.runner import SubprocessRunner

    leader = SubprocessRunner(tmp_path)
    t = ProcessTemplate(command=["sleep", "30"])
    h1 = leader.create("default/j", ReplicaType.MASTER, 0, t, {})
    standby = SubprocessRunner(tmp_path)  # snapshots pid of h1
    assert standby.get(h1.name).pid == h1.pid
    # The leader restarts the replica: new pid under the same name.
    leader.delete(h1.name, grace_seconds=1.0)
    h2 = leader.create("default/j", ReplicaType.MASTER, 0, t, {})
    assert h2.pid != h1.pid
    # Takeover: the standby must track the NEW incarnation, not classify
    # the old pid as dead and double-create.
    standby.rescan()
    got = standby.get(h2.name)
    assert got.pid == h2.pid
    assert got.is_active()
    standby.delete(h2.name, grace_seconds=1.0)
    leader.shutdown()


def test_startup_load_is_read_only(tmp_path):
    """Constructing a runner over another incarnation's records must not
    WRITE to them — a mere standby classifying a dead pid would clobber
    state the live leader still owns."""
    from pytorch_operator_tpu.api.types import ProcessTemplate, ReplicaType
    from pytorch_operator_tpu.controller.runner import SubprocessRunner

    leader = SubprocessRunner(tmp_path)
    t = ProcessTemplate(command=["sh", "-c", "exit 0"])
    h = leader.create("default/j", ReplicaType.MASTER, 0, t, {})
    assert wait_for(
        lambda: leader._read_exit_file(h.name) is not None, 15
    )
    rec_path = leader._record_path(h.name)
    before = rec_path.read_text()
    standby = SubprocessRunner(tmp_path)
    # In-memory classification happened...
    assert standby.get(h.name).is_finished()
    # ...but the record on disk is untouched (still says RUNNING).
    assert rec_path.read_text() == before
    leader.shutdown()
