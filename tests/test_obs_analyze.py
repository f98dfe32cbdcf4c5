"""Postmortem engine (obs/clock.py + obs/analyze.py + `tpujob why`).

Tentpole coverage for the cross-host postmortem PR:

- the heartbeat-matching clock-offset estimator: synthetic skewed hosts
  recover their offset/drift; jittered and dropped heartbeats are
  tolerated; the merged two-host trace orders rendezvous-join spans
  causally with skew residual under one heartbeat interval (the
  acceptance criterion);
- every detector rule firing on a crafted timeline — and NOT firing on
  a healthy one;
- the satellites: metric-series retirement bounds the registry under
  job churn, span ring/flush spec knobs thread env → recorder,
  histogram exemplars survive exposition round trips into `tpujob top`
  and the `why` report, top sort/filter helpers;
- the bench_smoke lane pin: analysis is OFFLINE-only (zero span records
  emitted by a whole run-plus-analysis with tracing disabled) and
  `tpujob why` on a healthy run reports zero findings.
"""

from __future__ import annotations

import json
import time

import pytest

from pytorch_operator_tpu import obs
from pytorch_operator_tpu.controller.store import key_to_fs
from pytorch_operator_tpu.obs import analyze as obs_analyze
from pytorch_operator_tpu.obs import clock as obs_clock
from pytorch_operator_tpu.obs import trace as obs_trace
from pytorch_operator_tpu.obs.clock import (
    ClockLog,
    estimate_job_offsets,
    estimate_offset,
    job_clock_log,
    load_observations,
    offsets_for_trace_files,
)

KEY = "default/pm"


# ---- artifact builders (the recorded surfaces `why` reads) ----


def _write_status(state, key, replica, recs) -> None:
    d = state / "status" / key_to_fs(key)
    d.mkdir(parents=True, exist_ok=True)
    with open(d / f"{replica}.jsonl", "a") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _beats(t0, n, interval, step0=1, step_time_ms=10.0, **extra):
    return [
        {
            "event": "progress",
            "ts": t0 + i * interval,
            "step": step0 + i,
            "steps_per_sec": 1000.0 / step_time_ms,
            "step_time_ms": step_time_ms,
            **extra,
        }
        for i in range(n)
    ]


def _write_events(state, key, evs) -> None:
    d = state / "events"
    d.mkdir(parents=True, exist_ok=True)
    with open(d / (key_to_fs(key) + ".events.jsonl"), "a") as f:
        for ts, etype, reason, msg in evs:
            f.write(
                json.dumps(
                    {
                        "timestamp": ts,
                        "type": etype,
                        "reason": reason,
                        "message": msg,
                        "count": 1,
                    }
                )
                + "\n"
            )


def _findings(state, key, window_s=None):
    report = obs_analyze.analyze(state, key, window_s=window_s)
    return report, [f["rule"] for f in report["findings"]]


# ---- clock-offset estimator ----


class TestClockEstimator:
    def test_constant_skew_recovered_exactly(self):
        # Worker clock 5s behind the supervisor, zero delay jitter.
        pairs = [(100.0 + i, 105.0 + i) for i in range(10)]
        est = estimate_offset(pairs)
        assert est.offset_s == pytest.approx(5.0, abs=1e-9)
        assert abs(est.drift_ppm) < 1.0
        assert est.residual_s < 1e-9

    def test_jittered_delays_tolerated(self):
        # Deterministic poll jitter in [0, 90ms]; true offset -3.2s.
        pairs = [
            (200.0 + 0.5 * i, 200.0 + 0.5 * i - 3.2 + ((i * 37) % 10) / 111.0)
            for i in range(40)
        ]
        est = estimate_offset(pairs)
        # The estimate absorbs at most ~the delay band, far under the
        # 0.5s heartbeat interval (the acceptance bound).
        assert abs(est.offset_s - (-3.2)) < 0.1
        assert est.residual_s < 0.1
        assert est.n == 40

    def test_drift_recovered(self):
        # 200 ppm rate error over a 1000s window + small jitter: the
        # drift-aware correction stays tight at BOTH ends of the window.
        drift = 200e-6
        pairs = [
            (s, s + 1.0 + drift * s + ((i * 13) % 7) / 700.0)
            for i, s in enumerate(range(0, 1000, 10))
        ]
        est = estimate_offset(pairs)
        assert 100.0 < est.drift_ppm < 300.0
        for s in (0.0, 500.0, 1000.0):
            true = 1.0 + drift * s
            assert abs(est.offset_at(s) - true) < 0.05

    def test_dropped_heartbeats_tolerated(self):
        # Keep only every third beat (drop_heartbeat-style gaps).
        pairs = [
            (100.0 + i, 100.0 + i + 2.5 + ((i * 29) % 5) / 200.0)
            for i in range(60)
            if i % 3 == 0
        ]
        est = estimate_offset(pairs)
        assert abs(est.offset_s - 2.5) < 0.05

    def test_no_pairs_is_none_and_few_pairs_no_drift(self):
        assert estimate_offset([]) is None
        est = estimate_offset([(1.0, 2.0), (2.0, 3.1)])
        assert est.drift_ppm == 0.0
        assert est.offset_s == pytest.approx(1.05, abs=0.06)

    def test_implausible_drift_collapses_to_pure_offset(self):
        # A short (1s) window turns delay jitter into a huge apparent
        # slope; the credibility clamp must zero it instead of
        # extrapolating garbage beyond the window.
        pairs = [
            (100.0 + i * 0.1, 100.0 + i * 0.1 + 1.0 + ((i * 7) % 3) / 50.0)
            for i in range(10)
        ]
        est = estimate_offset(pairs)
        assert est.drift_ppm == 0.0
        assert abs(est.offset_s - 1.0) < 0.05

    def test_log_roundtrip_and_rotation(self, tmp_path):
        path = job_clock_log(tmp_path, KEY)
        log = ClockLog(path, max_bytes=600)
        for i in range(20):
            log.observe("worker-0", 100.0 + i, 101.0 + i)
        obs_by_rep = load_observations(path)
        # The ring rotated (cap ~600B, ~85B/record) yet old + new
        # generations both load; newest pair present.
        assert path.with_suffix(".jsonl.1").exists()
        pairs = obs_by_rep["worker-0"]
        assert (119.0, 120.0) in pairs
        ests = estimate_job_offsets(tmp_path, KEY)
        assert ests["worker-0"].offset_s == pytest.approx(1.0, abs=1e-6)

    def test_supervisor_records_observations_with_priming(self, tmp_path):
        """First sight of a replica primes the dedup (a daemon restart
        must not pair a stale beat with a fresh observe time); the next
        beat is logged with a real observe timestamp."""
        from pytorch_operator_tpu.controller import FakeRunner
        from pytorch_operator_tpu.controller.supervisor import Supervisor

        sup = Supervisor(state_dir=tmp_path / "state", runner=FakeRunner())
        try:
            d = tmp_path / "state" / "status" / key_to_fs(KEY)
            _write_status(tmp_path / "state", KEY, "master-0",
                          _beats(100.0, 1, 0.5))
            sup._progress.poll(d)
            sup._record_clock_observations(KEY, d)
            assert load_observations(job_clock_log(tmp_path / "state", KEY)) == {}
            _write_status(tmp_path / "state", KEY, "master-0",
                          _beats(100.5, 1, 0.5, step0=2))
            sup._progress.poll(d)
            sup._record_clock_observations(KEY, d)
            got = load_observations(job_clock_log(tmp_path / "state", KEY))
            assert [s for s, _ in got["master-0"]] == [100.5]
            # Re-polling the same beat adds nothing (once per beat).
            sup._progress.poll(d)
            sup._record_clock_observations(KEY, d)
            assert len(load_observations(
                job_clock_log(tmp_path / "state", KEY))["master-0"]) == 1
        finally:
            sup.shutdown()


class TestTwoHostSkewMerge:
    """The acceptance e2e: a two-host synthetic-skew trace merge orders
    the rendezvous-join spans causally, skew residual under one
    heartbeat interval."""

    INTERVAL = 0.5
    SKEW = 2.0  # worker wall clock 2s BEHIND the supervisor/master host

    def _seed(self, tmp_path):
        state = tmp_path / "state"
        key = "default/skew"
        log = ClockLog(job_clock_log(state, key))
        for i in range(20):
            true = 100.0 + i * self.INTERVAL
            # Supervisor observes each beat a jittery-but-small delay
            # after the true send instant; the worker STAMPS its beat
            # on its own (skewed) clock.
            log.observe("worker-0", true - self.SKEW,
                        true + ((i * 37) % 10) / 150.0)
            log.observe("master-0", true, true + ((i * 23) % 10) / 150.0)
        trace_dir = state / "trace" / key_to_fs(key)
        rec_m = obs_trace.SpanRecorder(trace_dir, "master-0")
        # True order: the coordinator's join opens at t=100.0, the
        # worker joins at t=100.5 — but the worker's skewed clock
        # records 98.5, which naively merges FIRST.
        rec_m.emit("rendezvous_join", "rendezvous", 100.0, 0.2, src="master-0")
        rec_m.close()
        rec_w = obs_trace.SpanRecorder(trace_dir, "worker-0")
        rec_w.emit("rendezvous_join", "rendezvous", 100.5 - self.SKEW, 0.2,
                   src="worker-0")
        rec_w.close()
        return state, key, trace_dir

    def _joins(self, doc):
        return [
            e for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "rendezvous_join"
        ]

    def test_naive_merge_inverts_causality(self, tmp_path):
        state, key, trace_dir = self._seed(tmp_path)
        doc = obs_trace.merge_trace_files(obs_trace.span_files(trace_dir))
        assert [j["args"]["src"] for j in self._joins(doc)] == [
            "worker-0", "master-0"
        ]

    def test_estimated_offsets_restore_causal_order(self, tmp_path):
        state, key, trace_dir = self._seed(tmp_path)
        ests = estimate_job_offsets(state, key)
        # The worker's skew is recovered within the heartbeat interval.
        assert abs(ests["worker-0"].offset_s - self.SKEW) < self.INTERVAL
        assert ests["worker-0"].residual_s < self.INTERVAL
        paths = obs_trace.span_files(trace_dir)
        offsets = offsets_for_trace_files(paths, ests)
        doc = obs_trace.merge_trace_files(paths, clock_offsets=offsets)
        joins = self._joins(doc)
        assert [j["args"]["src"] for j in joins] == ["master-0", "worker-0"]
        # Residual bound on the corrected timestamp itself.
        worker_ts = next(
            j["ts"] for j in joins if j["args"]["src"] == "worker-0"
        )
        assert abs(worker_ts / 1e6 - 100.5) < self.INTERVAL
        # The merged doc is self-describing about the applied fix.
        corr = [
            m for m in doc["traceEvents"]
            if m.get("ph") == "M" and m.get("name") == "clock_sync_correction"
        ]
        assert corr and any(
            "worker-0" in m["args"]["file"] for m in corr
        )

    def test_trace_cli_applies_corrections(self, tmp_path, capsys):
        from pytorch_operator_tpu.client.cli import main

        state, key, trace_dir = self._seed(tmp_path)
        (state / "jobs").mkdir(parents=True, exist_ok=True)
        out = tmp_path / "t.json"
        assert main(
            ["--state-dir", str(state), "trace", "skew", "--out", str(out)]
        ) == 0
        assert "clock_sync" in capsys.readouterr().err
        doc = json.loads(out.read_text())
        assert [j["args"]["src"] for j in self._joins(doc)] == [
            "master-0", "worker-0"
        ]
        # --no-clock-sync keeps raw per-host timestamps.
        assert main(
            ["--state-dir", str(state), "trace", "skew", "--out", str(out),
             "--no-clock-sync"]
        ) == 0
        doc = json.loads(out.read_text())
        assert [j["args"]["src"] for j in self._joins(doc)] == [
            "worker-0", "master-0"
        ]


# ---- detector rules ----


class TestDetectors:
    def test_healthy_timeline_has_no_findings(self, tmp_path):
        state = tmp_path / "state"
        _write_status(state, KEY, "master-0", _beats(100.0, 20, 0.5))
        _write_status(
            state, KEY, "master-0",
            [{"event": "checkpoint_committed", "ts": 100.0 + s * 0.5,
              "step": s, "commit_ms": 5.0, "queue_depth": 0}
             for s in range(2, 21, 2)],
        )
        report, rules = _findings(state, KEY)
        assert rules == []
        assert report["replicas"]["master-0"]["beats"] == 20

    def test_step_time_regression_fires_with_evidence(self, tmp_path):
        state = tmp_path / "state"
        recs = _beats(100.0, 12, 0.5, step_time_ms=10.0)
        recs += _beats(106.0, 4, 0.5, step0=13, step_time_ms=40.0)
        _write_status(state, KEY, "master-0", recs)
        report, rules = _findings(state, KEY)
        assert "step_time_regression" in rules
        f = next(
            f for f in report["findings"]
            if f["rule"] == "step_time_regression"
        )
        assert f["metrics"]["recent_ms"] == pytest.approx(40.0)
        assert f["metrics"]["baseline_ms"] == pytest.approx(10.0)
        # Evidence cites the worst recent sample.
        ev = f["evidence"][0]
        assert ev["source"] == "status" and ev["step_time_ms"] == 40.0

    def test_window_bounds_the_regression_comparison(self, tmp_path):
        state = tmp_path / "state"
        recs = _beats(100.0, 12, 0.5, step_time_ms=10.0)
        recs += _beats(106.0, 4, 0.5, step0=13, step_time_ms=40.0)
        _write_status(state, KEY, "master-0", recs)
        # A window covering EVERYTHING leaves no baseline: no finding.
        _, rules = _findings(state, KEY, window_s=1000.0)
        assert "step_time_regression" not in rules
        # A 2s window isolates the slow tail against the earlier base.
        _, rules = _findings(state, KEY, window_s=2.0)
        assert "step_time_regression" in rules

    def test_feed_stall_dominance_fires(self, tmp_path):
        state = tmp_path / "state"
        _write_status(
            state, KEY, "master-0",
            _beats(100.0, 8, 0.5, step_time_ms=20.0, feed_stall_ms=15.0),
        )
        report, rules = _findings(state, KEY)
        assert rules == ["feed_stall_dominance"]
        f = report["findings"][0]
        assert f["metrics"]["share"] == pytest.approx(0.75)

    def test_checkpoint_lag_and_queue_growth_fire(self, tmp_path):
        state = tmp_path / "state"
        _write_status(state, KEY, "master-0", _beats(100.0, 30, 0.2))
        _write_status(
            state, KEY, "master-0",
            [{"event": "checkpoint_committed", "ts": 100.0 + i,
              "step": 2 + 2 * i, "commit_ms": 900.0, "queue_depth": 1 + i}
             for i in range(4)],
        )
        report, rules = _findings(state, KEY)
        assert rules.count("checkpoint_lag") == 2
        lag = next(
            f for f in report["findings"]
            if "trail" in f["summary"]
        )
        # Last trained step 30, last committed 8, cadence 2.
        assert lag["metrics"]["lag_steps"] == pytest.approx(22.0)
        assert lag["metrics"]["cadence_steps"] == pytest.approx(2.0)

    def test_heartbeat_silence_names_victim_before_kill(self, tmp_path):
        state = tmp_path / "state"
        _write_status(state, KEY, "master-0", _beats(100.0, 3, 0.5))
        _write_events(
            state, KEY,
            [(103.5, "Warning", "TPUJobHung",
              "no heartbeat for 2.5s; killing the hung world.")],
        )
        report, rules = _findings(state, KEY)
        assert "heartbeat_silence" in rules
        f = next(
            f for f in report["findings"] if f["rule"] == "heartbeat_silence"
        )
        assert f["severity"] == "critical"
        assert "master-0" in f["summary"]
        # Acceptance: the evidence records are timestamped BEFORE the
        # deadline kill.
        kill_ts = next(
            e["ts"] for e in f["evidence"] if e["source"] == "event"
        )
        for e in f["evidence"]:
            if e["source"] != "event":
                assert e["ts"] < kill_ts
        assert f["metrics"]["silence_s"] == pytest.approx(2.5)

    def test_partial_silence_without_kill(self, tmp_path):
        state = tmp_path / "state"
        _write_status(state, KEY, "worker-0", _beats(100.0, 21, 0.5))
        _write_status(state, KEY, "master-0", _beats(100.0, 4, 0.5))
        report, rules = _findings(state, KEY)
        assert "heartbeat_silence" in rules
        f = next(
            f for f in report["findings"] if f["rule"] == "heartbeat_silence"
        )
        assert "master-0" in f["summary"] and "worker-0" not in f["summary"]

    def test_straggler_fires_on_gang_spread(self, tmp_path):
        state = tmp_path / "state"
        _write_status(state, KEY, "master-0",
                      _beats(100.0, 8, 0.5, step_time_ms=10.0))
        _write_status(state, KEY, "worker-0",
                      _beats(100.0, 8, 0.5, step_time_ms=10.0))
        _write_status(state, KEY, "worker-1",
                      _beats(100.0, 8, 0.5, step_time_ms=26.0))
        report, rules = _findings(state, KEY)
        assert "straggler" in rules
        f = next(f for f in report["findings"] if f["rule"] == "straggler")
        assert "worker-1" in f["summary"]
        assert f["metrics"]["spread"] == pytest.approx(2.6)

    def test_clock_alignment_feeds_the_silence_rule(self, tmp_path):
        """A replica 30s AHEAD would look alive forever on raw
        timestamps; aligned, its silence is detected."""
        state = tmp_path / "state"
        skew = 30.0
        # worker-0 stamps beats on a clock 30s ahead; it stops at true
        # t=102 while master keeps beating to t=110.
        _write_status(state, KEY, "master-0", _beats(100.0, 21, 0.5))
        _write_status(state, KEY, "worker-0",
                      _beats(100.0 + skew, 5, 0.5))
        log = ClockLog(job_clock_log(state, KEY))
        for i in range(5):
            true = 100.0 + i * 0.5
            log.observe("worker-0", true + skew, true + 0.01)
            log.observe("master-0", true, true + 0.01)
        report, rules = _findings(state, KEY)
        assert "heartbeat_silence" in rules
        f = next(
            f for f in report["findings"] if f["rule"] == "heartbeat_silence"
        )
        assert "worker-0" in f["summary"]
        assert report["clock"]["worker-0"]["offset_s"] == pytest.approx(
            -skew, abs=0.1
        )


# ---- tpujob why CLI ----


class TestWhyCLI:
    def test_why_renders_and_writes_json(self, tmp_path, capsys):
        from pytorch_operator_tpu.client.cli import main

        state = tmp_path / "state"
        _write_status(state, "default/pm", "master-0", _beats(100.0, 3, 0.5))
        _write_events(
            state, "default/pm",
            [(103.5, "Warning", "TPUJobHung", "no heartbeat; killing.")],
        )
        out = tmp_path / "report.json"
        rc = main(["--state-dir", str(state), "why", "pm", "--out", str(out)])
        assert rc == 0
        text = capsys.readouterr().out
        assert "heartbeat_silence" in text and "master-0" in text
        report = json.loads(out.read_text())
        assert report["job"] == "default/pm"
        assert [f["rule"] for f in report["findings"]] == [
            "heartbeat_silence"
        ]

    def test_why_prints_what_each_replica_compiled(self, tmp_path, capsys):
        """The first_step and metrics records carry the compile cache's
        counts (runtime/backend.py); `why` prints the last of each."""
        from pytorch_operator_tpu.client.cli import main

        state = tmp_path / "state"
        _write_status(state, "default/pm", "master-0", _beats(100.0, 3, 0.5) + [
            {"event": "first_step", "ts": 100.2, "step": 0,
             "programs_compiled": 0, "programs_from_cache": 41},
            {"event": "metrics", "ts": 100.9, "step": 1,
             "programs_compiled": 9, "programs_from_cache": 41},
            {"event": "metrics", "ts": 101.4, "step": 3,
             "programs_compiled": 2, "programs_from_cache": 57},
        ])
        out = tmp_path / "report.json"
        assert main(["--state-dir", str(state), "why", "pm", "--out", str(out)]) == 0
        assert ("compiled: master-0 0 program(s) compiled and 41 from the cache to first step, "
                "2 program(s) compiled and 57 from the cache in all") in capsys.readouterr().out
        assert json.loads(out.read_text())["programs_compiled"] == {
            "master-0": {"to_first_step": [0, 41], "in_all": [2, 57]}
        }

    def test_why_errors_with_no_artifacts(self, tmp_path, capsys):
        from pytorch_operator_tpu.client.cli import main

        (tmp_path / "state" / "jobs").mkdir(parents=True)
        rc = main(["--state-dir", str(tmp_path / "state"), "why", "ghost"])
        assert rc == 1
        assert "no recorded artifacts" in capsys.readouterr().err


# ---- satellite: metric lifecycle (registry retirement) ----


class TestRetirement:
    def test_histogram_and_gauge_drop_series(self):
        from pytorch_operator_tpu.controller.metrics import Gauge
        from pytorch_operator_tpu.obs.metrics import Histogram

        h = Histogram("h")
        h.observe(0.1, job="a")
        h.observe(0.2, job="b")
        assert h.drop_series("job", "a") == 1
        assert h.series_count() == 1 and h.count(job="b") == 1
        g = Gauge("g")
        g.set(1.0, job="a")
        g.set(2.0, job="b", unit="x")
        assert g.drop_series("job", "b") == 1
        assert g.get(job="a") == 1.0

    def test_job_churn_leaves_registry_bounded(self, tmp_path):
        """The ROADMAP unbounded-cardinality fix: submit+observe+delete
        N jobs; the registry ends no bigger than it started."""
        from pytorch_operator_tpu.controller import FakeRunner
        from pytorch_operator_tpu.controller.supervisor import Supervisor
        from tests.testutil import new_job

        sup = Supervisor(state_dir=tmp_path / "state", runner=FakeRunner())
        try:
            def churn(i: int) -> None:
                key = sup.submit(new_job(name=f"churn-{i}", workers=0))
                m = sup.metrics
                m.step_time_seconds.observe(0.01, job=key)
                m.checkpoint_commit_seconds.observe(0.01, job=key)
                m.job_step.set(float(i), job=key)
                m.job_progress_age.set(0.1, job=key)
                assert sup.delete_job(key)

            # One warm-up fills the job-independent series (store
            # persist latency etc.); churn must not grow past it.
            churn(0)
            baseline = sup.metrics.series_count()
            for i in range(1, 25):
                churn(i)
            assert sup.metrics.series_count() <= baseline
            assert sup.metrics.step_time_seconds.series_count() == 0
            # The supervisor-side fold state retired with the series.
            assert sup._hb_observed == {} and sup._clock_seen == {}
        finally:
            sup.shutdown()


# ---- satellite: span ring / flush cadence spec knobs ----


class TestObservabilityKnobs:
    def test_policy_roundtrip_and_validation(self):
        from pytorch_operator_tpu.api import ObservabilityPolicy
        from pytorch_operator_tpu.api.validation import validate
        from pytorch_operator_tpu.api.types import TPUJob
        from tests.testutil import new_job

        p = ObservabilityPolicy(
            trace=True, trace_ring_bytes=65536, trace_flush_every=4
        )
        assert ObservabilityPolicy.from_dict(p.to_dict()) == p
        assert ObservabilityPolicy.from_dict({}).trace_ring_bytes == 0
        job = new_job(name="knobs", workers=0)
        job.spec.observability = ObservabilityPolicy(trace_ring_bytes=-1)
        with pytest.raises(Exception):
            validate(job)

    def test_env_threads_knobs_only_when_traced(self):
        from pytorch_operator_tpu.api import ObservabilityPolicy, ReplicaType
        from pytorch_operator_tpu.runtime.env import build_cluster_env
        from tests.testutil import new_job

        job = new_job(name="knobs", workers=0)
        job.spec.observability = ObservabilityPolicy(
            trace=True, trace_ring_bytes=65536, trace_flush_every=4
        )
        env = build_cluster_env(
            job, ReplicaType.MASTER, 0, trace_dir="/tmp/t"
        )
        assert env["TPUJOB_TRACE_RING_BYTES"] == "65536"
        assert env["TPUJOB_TRACE_FLUSH_EVERY"] == "4"
        env = build_cluster_env(job, ReplicaType.MASTER, 0)  # not traced
        assert "TPUJOB_TRACE_RING_BYTES" not in env

    def test_tracer_honors_env_knobs(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs_trace.ENV_VAR, str(tmp_path / "t"))
        monkeypatch.setenv(obs_trace.RING_BYTES_ENV, "4096")
        monkeypatch.setenv(obs_trace.FLUSH_EVERY_ENV, "1")
        obs_trace.reset_tracer()
        try:
            rec = obs.tracer()
            assert rec.max_bytes == 4096 and rec.flush_every == 1
            # flush_every=1: the record is on disk with no flush() call.
            rec.emit("s", "cat", time.time(), 0.001)
            assert len(
                [e for e in obs_trace.load_span_file(rec.path)
                 if e["ph"] == "X"]
            ) == 1
        finally:
            monkeypatch.delenv(obs_trace.ENV_VAR, raising=False)
            monkeypatch.delenv(obs_trace.RING_BYTES_ENV, raising=False)
            monkeypatch.delenv(obs_trace.FLUSH_EVERY_ENV, raising=False)
            obs_trace.reset_tracer()

    def test_malformed_env_knobs_fall_back(self, monkeypatch):
        monkeypatch.setenv(obs_trace.RING_BYTES_ENV, "not-a-number")
        assert obs_trace._env_int(
            obs_trace.RING_BYTES_ENV, obs_trace.DEFAULT_MAX_BYTES
        ) == obs_trace.DEFAULT_MAX_BYTES
        monkeypatch.setenv(obs_trace.RING_BYTES_ENV, "-5")
        assert obs_trace._env_int(obs_trace.RING_BYTES_ENV, 7) == 7


# ---- satellite: exemplar linking ----


class TestExemplars:
    def test_observe_render_parse_roundtrip(self):
        from pytorch_operator_tpu.obs.metrics import (
            Histogram,
            parse_exemplars,
            parse_prometheus_text,
        )

        h = Histogram("tpujob_step_time_seconds")
        h.observe(0.01, exemplar="master-0/step:3", job="j")
        h.observe(0.3, exemplar="master-0/step:7", job="j")
        h.observe(0.31, job="j")  # no exemplar: keeps the last one
        text = h.render()
        assert '# {span_id="master-0/step:7"}' in text
        # The exemplar suffix must not break plain bucket parsing.
        parsed = parse_prometheus_text(text)
        from tests.testutil import assert_histogram_conformant

        assert_histogram_conformant(parsed, "tpujob_step_time_seconds")
        ex = parse_exemplars(text)["tpujob_step_time_seconds_bucket"]
        by_span = {span: v for _labels, span, v in ex}
        assert by_span == {
            "master-0/step:3": 0.01, "master-0/step:7": 0.3
        }
        assert h.exemplars(job="j")["0.5"] == ("master-0/step:7", 0.3)

    def test_top_surfaces_p99_exemplar(self, tmp_path):
        from pytorch_operator_tpu.controller.store import JobStore
        from pytorch_operator_tpu.obs import top
        from pytorch_operator_tpu.obs.metrics import Histogram
        from tests.testutil import new_job

        state = tmp_path / "state"
        store = JobStore(persist_dir=state / "jobs")
        key = store.add(new_job(name="ex", workers=0))
        _write_status(state, key, "master-0", _beats(time.time(), 2, 0.5))
        h = Histogram(top.STEP_HIST)
        h.observe(0.01, exemplar="master-0/step:1", job=key)
        h.observe(0.4, exemplar="master-0/step:2", job=key)
        (state / "metrics.prom").write_text(h.render() + "\n")
        rows = top.gather_rows(state)
        assert rows[0]["p99_span"] == "master-0/step:2"
        assert "master-0/step:2" in top.render_table(rows)

    def test_supervisor_fold_attaches_exemplars(self, tmp_path):
        from pytorch_operator_tpu.controller import FakeRunner
        from pytorch_operator_tpu.controller.supervisor import Supervisor
        from tests.testutil import new_job

        sup = Supervisor(state_dir=tmp_path / "state", runner=FakeRunner())
        try:
            key = sup.submit(new_job(name="exf", workers=0))
            # First sync creates the job (and resets its status dir —
            # beats must land after, as they do in a live world).
            sup.sync_once()
            now = time.time()
            _write_status(
                tmp_path / "state", key, "master-0",
                [{"event": "progress", "ts": now, "step": 9,
                  "steps_per_sec": 100.0, "step_time_ms": 10.0},
                 {"event": "checkpoint_committed", "ts": now, "step": 8,
                  "commit_ms": 3.0, "queue_depth": 0}],
            )
            sup.sync_once()
            assert sup.metrics.step_time_seconds.exemplars(job=key)
            ids = [
                e[0]
                for e in sup.metrics.step_time_seconds.exemplars(
                    job=key
                ).values()
            ]
            assert ids == ["master-0/step:9"]
            ck = sup.metrics.checkpoint_commit_seconds.exemplars(job=key)
            assert [e[0] for e in ck.values()] == ["master-0/ckpt_commit:8"]
        finally:
            sup.shutdown()


# ---- satellite: top sort/filter helpers ----


class TestTopKeys:
    ROWS = [
        {"job": "default/alpha", "step": 10, "steps_per_sec": 2.0,
         "p50_ms": 5.0, "p99_ms": 9.0, "ckpt_lag": 1,
         "feed_stall_ms": None, "age_s": 3.0, "restarts": 0,
         "p99_span": None},
        {"job": "default/beta", "step": 99, "steps_per_sec": 8.0,
         "p50_ms": None, "p99_ms": None, "ckpt_lag": 4,
         "feed_stall_ms": 0.5, "age_s": 1.0, "restarts": 2,
         "p99_span": "m/step:9"},
        {"job": "prod/gamma", "step": None, "steps_per_sec": None,
         "p50_ms": 7.0, "p99_ms": 30.0, "ckpt_lag": None,
         "feed_stall_ms": 2.0, "age_s": None, "restarts": 1,
         "p99_span": None},
    ]

    def test_sort_numeric_none_last(self):
        from pytorch_operator_tpu.obs.top import sort_rows

        got = [r["job"] for r in sort_rows(list(self.ROWS), "steps_per_sec")]
        assert got == ["default/beta", "default/alpha", "prod/gamma"]
        got = [
            r["job"]
            for r in sort_rows(list(self.ROWS), "steps_per_sec",
                               reverse=False)
        ]
        assert got == ["default/alpha", "default/beta", "prod/gamma"]

    def test_sort_default_is_identity(self):
        from pytorch_operator_tpu.obs.top import sort_rows

        assert sort_rows(list(self.ROWS), None) == self.ROWS

    def test_filter_substring_case_insensitive(self):
        from pytorch_operator_tpu.obs.top import filter_rows, render_table

        got = filter_rows(list(self.ROWS), "DEFAULT")
        assert [r["job"] for r in got] == ["default/alpha", "default/beta"]
        assert filter_rows(list(self.ROWS), None) == self.ROWS
        text = render_table([], filter_str="zzz")
        assert "no jobs matching" in text

    def test_render_marks_sorted_column(self):
        from pytorch_operator_tpu.obs.top import render_table

        text = render_table(list(self.ROWS), sort_key="ckpt_lag")
        assert "CKPT LAG ▾" in text


# ---- bench_smoke lane: analysis is offline-only, healthy = clean ----


@pytest.mark.bench_smoke
def test_why_is_offline_and_clean_on_healthy_run(tmp_path, capsys):
    """Two pins in one real run: (1) with tracing disabled, the whole
    run PLUS the analysis emits zero span records (analysis adds zero
    step-path span/metric calls — it reads artifacts only); (2) `tpujob
    why` on a healthy world reports zero findings."""
    from pytorch_operator_tpu.api import (
        ObjectMeta, ProcessTemplate, ReplicaSpec, ReplicaType,
        RestartPolicy, TPUJob, TPUJobSpec, set_defaults,
    )
    from pytorch_operator_tpu.client.cli import main
    from pytorch_operator_tpu.controller.supervisor import Supervisor

    obs_trace.reset_tracer()
    records_before = obs.records_emitted()
    job = TPUJob(
        metadata=ObjectMeta(name="healthy"),
        spec=TPUJobSpec(
            replica_specs={
                ReplicaType.MASTER: ReplicaSpec(
                    replicas=1,
                    restart_policy=RestartPolicy.ON_FAILURE,
                    template=ProcessTemplate(
                        module="pytorch_operator_tpu.workloads.exit_with",
                        args=["--steps", "8", "--step-time", "0.02"],
                    ),
                ),
            },
        ),
    )
    set_defaults(job)
    state = tmp_path / "state"
    sup = Supervisor(state_dir=state, poll_interval=0.02)
    try:
        key = sup.submit(job)
        deadline = time.time() + 60
        while time.time() < deadline:
            sup.sync_once()
            j = sup.store.get(key)
            if j is None or j.is_finished():
                break
            time.sleep(0.02)
        sup.write_metrics_file()
        series_after_run = sup.metrics.series_count()
    finally:
        sup.shutdown()
    assert j is not None and j.is_succeeded()

    report = obs_analyze.analyze(state, key)
    assert report["findings"] == []
    assert report["replicas"]["master-0"]["beats"] >= 4
    # The estimator got real observation pairs from the daemon fold.
    assert report["clock"].get("master-0", {}).get("n", 0) >= 1
    # Offline pins: zero span records emitted by run+analysis with
    # tracing disabled, and analysis minted no new metric series.
    assert obs.records_emitted() == records_before
    assert sup.metrics.series_count() == series_after_run
    # The CLI face agrees.
    assert main(["--state-dir", str(state), "why", "healthy"]) == 0
    assert "no findings" in capsys.readouterr().out


# ---- chaos e2e: the ROADMAP drop_heartbeat world, fed to `why` ----


@pytest.mark.chaos
def test_why_names_hung_replica_from_chaos_world(tmp_path, capsys):
    """Acceptance e2e: the drop_heartbeat + hang-deadline chaos world,
    fed to `tpujob why`, names the hung replica and the
    heartbeat-silence finding, with evidence timestamped BEFORE the
    deadline kill."""
    from pytorch_operator_tpu import faults
    from pytorch_operator_tpu.api import (
        ObjectMeta, ObservabilityPolicy, ProcessTemplate, ReplicaSpec,
        ReplicaType, RestartPolicy, RunPolicy, TPUJob, TPUJobSpec,
        set_defaults,
    )
    from pytorch_operator_tpu.api.defaults import HANG_DEADLINE_ANNOTATION
    from pytorch_operator_tpu.client.cli import main
    from pytorch_operator_tpu.controller.supervisor import Supervisor
    from pytorch_operator_tpu.faults import Fault, FaultPlan

    faults.disarm()
    state = tmp_path / "state"
    sup = Supervisor(state_dir=state, poll_interval=0.05)
    key = "default/hang-why"
    try:
        faults.arm(FaultPlan(seed=1, faults=[
            Fault(kind="drop_heartbeat", target="master-0",
                  nth=3, times=100000),
        ]))
        job = TPUJob(
            metadata=ObjectMeta(
                name="hang-why",
                annotations={HANG_DEADLINE_ANNOTATION: "2"},
            ),
            spec=TPUJobSpec(
                replica_specs={
                    ReplicaType.MASTER: ReplicaSpec(
                        replicas=1,
                        restart_policy=RestartPolicy.ON_FAILURE,
                        template=ProcessTemplate(
                            module="pytorch_operator_tpu.workloads.exit_with",
                            args=["--steps", "400", "--step-time", "0.05"],
                        ),
                    ),
                },
                run_policy=RunPolicy(backoff_limit=0),
                # Trace the casualty so the silence finding can cite
                # SPAN evidence, not just status records.
                observability=ObservabilityPolicy(trace=True),
            ),
        )
        set_defaults(job)
        sup.submit(job)
        deadline = time.time() + 30
        while time.time() < deadline:
            sup.sync_once()
            j = sup.store.get(key)
            if j is None or j.is_finished():
                break
            time.sleep(0.05)
    finally:
        faults.disarm()
        sup.shutdown()
    assert j is not None and j.is_failed()

    report = obs_analyze.analyze(state, key)
    silence = [
        f for f in report["findings"] if f["rule"] == "heartbeat_silence"
    ]
    assert silence, f"no heartbeat_silence finding in {report['findings']}"
    f = silence[0]
    assert "master-0" in f["summary"]
    kill_ts = next(
        e["ts"] for e in f["evidence"] if e["source"] == "event"
    )
    pre_kill = [e for e in f["evidence"] if e["source"] != "event"]
    assert pre_kill and all(e["ts"] < kill_ts for e in pre_kill)
    # The evidence includes the victim's last step SPAN (traced world),
    # also timestamped before the kill.
    span_ev = [e for e in f["evidence"] if e["source"] == "span"]
    assert span_ev and span_ev[0]["name"] == "step"
    assert span_ev[0]["ts"] < kill_ts
    # The terminal report tells the same story.
    assert main(["--state-dir", str(state), "why", "hang-why"]) == 0
    out = capsys.readouterr().out
    assert "heartbeat_silence" in out and "master-0" in out
