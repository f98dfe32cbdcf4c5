"""Flight-recorder end-to-end, with real subprocess worlds.

- ``tpujob run --trace`` + ``tpujob trace <job>`` emits one valid
  Chrome-trace JSON containing spans from every instrumented layer
  (supervisor pass, per-job reconcile, replica step loop, rendezvous
  join, async checkpoint commit) — the acceptance-criteria schema check.
- A live run's ``/metrics`` serves step-time, sync-pass, reconcile, and
  checkpoint-commit histograms with correct bucket/count/sum invariants.
- The ROADMAP chaos scenario: ``drop_heartbeat`` + hang-deadline with a
  real subprocess casualty — the ``tpujob_job_progress_age`` gauge and
  the step-time histogram must SHOW the hang before the deadline kill
  fires (the whole point of the observability layer: the operator sees
  the stall before the controller acts on it).
"""

from __future__ import annotations

import json
import os
import time

import pytest

from pytorch_operator_tpu import faults, obs
from pytorch_operator_tpu.api import (
    ObjectMeta,
    ProcessTemplate,
    ReplicaSpec,
    ReplicaType,
    RestartPolicy,
    RunPolicy,
    TPUJob,
    TPUJobSpec,
    set_defaults,
)
from pytorch_operator_tpu.api.defaults import HANG_DEADLINE_ANNOTATION
from pytorch_operator_tpu.controller.supervisor import Supervisor
from pytorch_operator_tpu.faults import Fault, FaultPlan
from pytorch_operator_tpu.obs.metrics import parse_prometheus_text
from tests.testutil import assert_histogram_conformant

TRACE_JOB = """\
api_version: tpujob.dev/v1
kind: TPUJob
metadata:
  name: traced-e2e
spec:
  replica_specs:
    Master:
      replicas: 1
      restart_policy: OnFailure
      template:
        module: pytorch_operator_tpu.workloads.exit_with
        args: ["--steps", "6", "--step-time", "0.02",
               "--async-checkpoint", "--commit-time", "0.005"]
"""


def _exit_with_job(name: str, args, annotations=None, backoff=None) -> TPUJob:
    job = TPUJob(
        metadata=ObjectMeta(name=name, annotations=dict(annotations or {})),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.MASTER: ReplicaSpec(
                    replicas=1,
                    restart_policy=RestartPolicy.ON_FAILURE,
                    template=ProcessTemplate(
                        module="pytorch_operator_tpu.workloads.exit_with",
                        args=[str(a) for a in args],
                    ),
                ),
            },
            run_policy=RunPolicy(backoff_limit=backoff),
        ),
    )
    set_defaults(job)
    return job


def _validate_chrome_trace(doc: dict) -> list:
    """The acceptance-criteria schema check: a loadable Chrome-trace
    document — ``traceEvents`` list, every event named with a phase,
    complete (``X``) events carrying numeric ts/dur/pid/tid in
    microseconds, sorted by ts. Returns the complete spans."""
    assert isinstance(doc, dict) and isinstance(doc["traceEvents"], list)
    spans = []
    for ev in doc["traceEvents"]:
        assert isinstance(ev, dict)
        assert isinstance(ev.get("name"), str) and ev["name"]
        assert ev.get("ph") in ("X", "M", "i")
        if ev["ph"] == "X":
            for field in ("ts", "dur", "pid", "tid"):
                assert isinstance(ev.get(field), (int, float)), (field, ev)
            assert ev["dur"] >= 0
            spans.append(ev)
    assert [s["ts"] for s in spans] == sorted(s["ts"] for s in spans)
    return spans


def test_trace_export_covers_all_layers(tmp_path, capsys):
    """``tpujob run --trace`` then ``tpujob trace``: one merged
    Perfetto-loadable JSON with spans from the supervisor pass, the
    replica step loop, the rendezvous join, and the async checkpoint
    commit (>= 3 layers required; all 4 asserted)."""
    from pytorch_operator_tpu.client.cli import main

    state = tmp_path / "state"
    job = tmp_path / "job.yaml"
    job.write_text(TRACE_JOB)
    try:
        rc = main(
            ["--state-dir", str(state), "run", str(job),
             "--trace", "--timeout", "60"]
        )
        # Foreground `run` syncs only its own job (no full passes, by
        # design — it must not reconcile a daemon's jobs). Drive one
        # daemon-style pass with the tracer still armed so the
        # supervisor PASS phases land in the trace too.
        sup = Supervisor(state_dir=state)
        sup.sync_once()
        sup.shutdown()
        rec = obs.tracer()
        if rec is not None:
            rec.flush()
    finally:
        # `run --trace` arms the PROCESS tracer via the env; a test
        # process must disarm it or every later test records spans.
        os.environ.pop("TPUJOB_TRACE_DIR", None)
        obs.reset_tracer()
    assert rc == 0
    capsys.readouterr()

    out = tmp_path / "trace.json"
    assert main(
        ["--state-dir", str(state), "trace", "traced-e2e", "--out", str(out)]
    ) == 0
    said = capsys.readouterr()
    assert "perfetto" in said.out.lower()
    # The self time of each layer's spans, from their `parent` links.
    assert "self time by span (ms)" in said.err and " step " in said.err
    doc = json.loads(out.read_text())
    spans = _validate_chrome_trace(doc)

    by_cat = {}
    for s in spans:
        by_cat.setdefault(s.get("cat", ""), set()).add(s["name"])
    # Layer 1: supervisor pass phases + per-job reconciles.
    assert "pass_serial" in by_cat["supervisor"]
    assert "reconcile" in by_cat["supervisor"]
    # Layer 2: the replica step loop (6 steps, each with its arg).
    step_spans = [s for s in spans if s["name"] == "step"]
    assert {s["args"]["step"] for s in step_spans} == {1, 2, 3, 4, 5, 6}
    # Layer 3: the rendezvous join (replica side).
    assert "rendezvous_join" in by_cat["rendezvous"]
    # Layer 4: async checkpoint commits on the writer thread, with real
    # duration (--commit-time 0.005 => >= ~5ms each).
    commits = [s for s in spans if s["name"] == "ckpt_commit"]
    assert len(commits) == 6
    assert all(c["dur"] >= 4000 for c in commits)
    # Supervisor and replica spans come from different processes, and
    # the metadata names both.
    pids = {s["pid"] for s in spans}
    assert len(pids) >= 2
    proc_names = {
        m["args"]["name"]
        for m in doc["traceEvents"]
        if m.get("ph") == "M" and m.get("name") == "process_name"
    }
    assert "supervisor" in proc_names
    assert any(n.startswith("master-0") for n in proc_names)


def test_trace_cmd_errors_without_span_files(tmp_path, capsys):
    from pytorch_operator_tpu.client.cli import main

    state = tmp_path / "state"
    (state / "jobs").mkdir(parents=True)
    assert main(["--state-dir", str(state), "trace", "ghost"]) == 1
    assert "no span files" in capsys.readouterr().err


def test_live_metrics_serves_conformant_histograms(tmp_path):
    """After a real async-checkpointing world runs to completion under
    an in-process supervisor, /metrics (render_text) carries step-time,
    sync-pass, reconcile, store-persist, and checkpoint-commit
    histograms that satisfy the Prometheus invariants — and the
    metrics.prom snapshot `tpujob top` reads is the same text."""
    sup = Supervisor(state_dir=tmp_path / "state", poll_interval=0.05)
    try:
        job = _exit_with_job(
            "metrics-e2e",
            ["--steps", "10", "--step-time", "0.05",
             "--async-checkpoint", "--commit-time", "0.01"],
        )
        key = sup.submit(job)
        # Daemon-style passes (sync_once folds the heartbeat gauges and
        # histograms; foreground wait() would sync only the job). The
        # per-job gauges are live-only (cleared once the job finishes),
        # so sample their high-water marks DURING the run.
        deadline = time.time() + 60
        done = None
        ckpt_step_seen = 0.0
        while time.time() < deadline:
            sup.sync_once()
            ckpt_step_seen = max(
                ckpt_step_seen, sup.metrics.job_checkpoint_step.get(job=key)
            )
            done = sup.store.get(key)
            if done is None or done.is_finished():
                break
            time.sleep(0.05)
        assert done is not None and done.is_succeeded()
        sup.write_metrics_file()
        text = sup.metrics.render_text()
    finally:
        sup.shutdown()
    parsed = parse_prometheus_text(text)
    for name in (
        "tpujob_step_time_seconds",
        "tpujob_sync_pass_seconds",
        "tpujob_reconcile_seconds",
        "tpujob_store_persist_seconds",
        "tpujob_checkpoint_commit_seconds",
    ):
        assert_histogram_conformant(parsed, name)
    # The step-time fold is per-job and interval-averaged: ~20/s beats.
    key = "default/metrics-e2e"
    assert sup.metrics.step_time_seconds.count(job=key) >= 1
    q = sup.metrics.step_time_seconds.quantile(0.5, job=key)
    assert 0.01 < q < 1.0
    # Commit telemetry rode the status channel into the histogram and
    # the companion gauge (live value sampled mid-run above).
    assert sup.metrics.checkpoint_commit_seconds.count(job=key) >= 1
    assert ckpt_step_seen >= 1
    # The live-I/O mirror counters fold (rescan-free run: persist
    # writes happened, so the store-write counter must be nonzero).
    assert sup.metrics.store_io["writes"].get() > 0
    assert sup.metrics.progress_io["file_reads"].get() > 0
    # metrics.prom is the same exposition `tpujob top` parses.
    prom = (tmp_path / "state" / "metrics.prom").read_text()
    assert_histogram_conformant(
        parse_prometheus_text(prom), "tpujob_step_time_seconds"
    )


@pytest.mark.chaos
def test_drop_heartbeat_hang_shows_on_surfaces_before_deadline_kill(tmp_path):
    """ROADMAP chaos scenario, now with a real subprocess casualty: a
    fault plan drops every heartbeat after the second one, the job's
    hang-deadline is 2s — ``tpujob_job_progress_age`` must climb past
    1s (and the step-time histogram must hold the pre-hang beats) WHILE
    the job is still Running and unkilled; only then may the deadline
    kill fire (backoff_limit=0 => TPUJobHung failure)."""
    faults.disarm()
    sup = Supervisor(state_dir=tmp_path / "state", poll_interval=0.05)
    key = "default/hang-e2e"
    try:
        faults.arm(FaultPlan(seed=1, faults=[
            Fault(kind="drop_heartbeat", target="master-0",
                  nth=3, times=100000),
        ]))
        job = _exit_with_job(
            "hang-e2e",
            ["--steps", "400", "--step-time", "0.05"],
            annotations={HANG_DEADLINE_ANNOTATION: "2"},
            backoff=0,
        )
        sup.submit(job)
        hang_visible = False
        deadline = time.time() + 30
        while time.time() < deadline:
            sup.sync_once()
            j = sup.store.get(key)
            if j is None or j.is_finished():
                break
            age = sup.metrics.job_progress_age.get(job=key)
            beats = sup.metrics.step_time_seconds.count(job=key)
            if not hang_visible and age > 1.0 and beats >= 1:
                # The surfaces show the hang — and the kill has NOT
                # fired yet: the operator sees it first.
                assert "TPUJobHung" not in [
                    e.reason for e in sup.events.for_job(key)
                ]
                hang_visible = True
            time.sleep(0.05)
        j = sup.store.get(key)
        reasons = [e.reason for e in sup.events.for_job(key)]
    finally:
        faults.disarm()
        sup.shutdown()
    assert hang_visible, "progress-age gauge never showed the hang"
    assert "TPUJobHung" in reasons
    assert j is not None and j.is_failed()
    # The pre-hang heartbeats made it into the distribution; the hang
    # itself (no heartbeats) added nothing after.
    assert sup.metrics.step_time_seconds.count(job=key) >= 1
