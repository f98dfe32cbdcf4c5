"""Prometheus-style counters for the supervisor.

Reference: promauto counters (jobs created/succeeded/failed/restarted) served
on ``--monitoring-port`` (SURVEY.md §2 "Metrics"). Locally: an in-process
registry rendered in Prometheus text exposition format via the CLI or an
optional HTTP endpoint.
"""

from __future__ import annotations

import threading
from typing import Dict, Tuple


def _fmt_labels(key: Tuple[Tuple[str, str], ...]) -> str:
    """Prometheus exposition label block with the spec's escaping (a queue
    name is arbitrary user text; an unescaped quote would invalidate the
    whole scrape)."""
    esc = lambda v: str(v).replace("\\", r"\\").replace('"', r"\"").replace("\n", r"\n")  # noqa: E731
    return ",".join(f'{k}="{esc(v)}"' for k, v in key)


class Counter:
    """A labeled monotonic counter."""

    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0, **labels: str) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def get(self, **labels: str) -> float:
        key = tuple(sorted(labels.items()))
        with self._lock:
            return self._values.get(key, 0.0)

    def render(self) -> str:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} counter")
        with self._lock:
            if not self._values:
                lines.append(f"{self.name} 0")
            for key, value in sorted(self._values.items()):
                if key:
                    lines.append(f"{self.name}{{{_fmt_labels(key)}}} {value:g}")
                else:
                    lines.append(f"{self.name} {value:g}")
        return "\n".join(lines)


class Gauge:
    """A labeled settable gauge (point-in-time scheduler state)."""

    def __init__(self, name: str, help_text: str = ""):
        self.name = name
        self.help = help_text
        self._values: Dict[Tuple[Tuple[str, str], ...], float] = {}
        self._lock = threading.Lock()

    def set(self, value: float, **labels: str) -> None:
        key = tuple(sorted(labels.items()))
        with self._lock:
            self._values[key] = float(value)

    def clear(self) -> None:
        """Drop all series (stale labeled values must not linger)."""
        with self._lock:
            self._values.clear()

    def drop_series(self, label: str, value: str) -> int:
        """Retire every series carrying ``label == value`` (a deleted
        job's per-job gauges). Returns the count dropped."""
        pair = (label, str(value))
        with self._lock:
            doomed = [k for k in self._values if pair in k]
            for k in doomed:
                del self._values[k]
        return len(doomed)

    def series_count(self) -> int:
        with self._lock:
            return len(self._values)

    def get(self, **labels: str) -> float:
        key = tuple(sorted(labels.items()))
        with self._lock:
            return self._values.get(key, 0.0)

    def render(self) -> str:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} gauge")
        with self._lock:
            if not self._values:
                lines.append(f"{self.name} 0")
            for key, value in sorted(self._values.items()):
                if key:
                    lines.append(f"{self.name}{{{_fmt_labels(key)}}} {value:g}")
                else:
                    lines.append(f"{self.name} {value:g}")
        return "\n".join(lines)


class MetricsRegistry:
    """Registry of supervisor counters (reference counter set + replica ops)."""

    def __init__(self) -> None:
        self._counters: Dict[str, Counter] = {}
        self._histograms: Dict[str, object] = {}
        self.jobs_created = self.counter(
            "tpujob_jobs_created_total", "TPUJobs accepted by the supervisor"
        )
        self.jobs_succeeded = self.counter(
            "tpujob_jobs_succeeded_total", "TPUJobs that reached Succeeded"
        )
        self.jobs_failed = self.counter(
            "tpujob_jobs_failed_total", "TPUJobs that reached Failed"
        )
        self.jobs_restarted = self.counter(
            "tpujob_jobs_restarted_total", "Replica restarts across all TPUJobs"
        )
        self.jobs_preempted = self.counter(
            "tpujob_jobs_preempted_total",
            "TPUJob worlds evicted for higher-priority gangs",
        )
        # ---- elastic in-place resize (controller/elastic.py) ----
        self.elastic_resizes = self.counter(
            "tpujob_elastic_resizes_total",
            "In-place world resizes (shrink or spare-backfill) that "
            "spent NO restart — the resize-vs-restart ledger's fast side",
        )
        self.replicas_created = self.counter(
            "tpujob_replicas_created_total", "Replica processes launched"
        )
        self.replicas_deleted = self.counter(
            "tpujob_replicas_deleted_total", "Replica processes terminated"
        )
        self.replicas_failed = self.counter(
            "tpujob_replicas_failed_total", "Replica processes that exited nonzero"
        )
        self._gauges: Dict[str, Gauge] = {}
        self.jobs_active = self.gauge(
            "tpujob_jobs_active", "Unfinished TPUJobs in the store"
        )
        self.replicas_active = self.gauge(
            "tpujob_replicas_active", "Live replica processes"
        )
        self.slots_used = self.gauge(
            "tpujob_slots_used", "Device slots occupied by live replicas"
        )
        self.slots_capacity = self.gauge(
            "tpujob_slots_capacity", "Device-slot capacity (--max-slots; 0 = unbounded)"
        )
        self.gangs_held = self.gauge(
            "tpujob_gangs_held", "Gangs held Unschedulable in the last pass"
        )
        self.world_size = self.gauge(
            "tpujob_world_size",
            "Current world size (live replicas incl. Master) per elastic "
            "job, labeled with the submitted target",
        )
        self.hot_spares = self.gauge(
            "tpujob_hot_spares",
            "Warm standby processes ready for promotion (runner pool)",
        )
        self.queue_slots_used = self.gauge(
            "tpujob_queue_slots_used", "Device slots in use per queue"
        )
        self.queue_slots_capacity = self.gauge(
            "tpujob_queue_slots_capacity", "Per-queue device-slot caps (--queue-slots)"
        )
        # Live workload telemetry (SURVEY §5 "steps/sec + images/sec/chip
        # meters"): folded from the newest per-replica progress heartbeat
        # each sync pass (controller/progress.py).
        self.job_step = self.gauge(
            "tpujob_job_step", "Latest reported training step per job"
        )
        self.job_steps_per_sec = self.gauge(
            "tpujob_job_steps_per_sec", "Live training steps/sec per job"
        )
        self.job_throughput = self.gauge(
            "tpujob_job_throughput",
            "Live training throughput per job (unit label = e.g. "
            "images/sec/chip, tokens/sec/chip)",
        )
        self.job_loss = self.gauge(
            "tpujob_job_loss", "Latest reported training loss per job"
        )
        self.job_progress_age = self.gauge(
            "tpujob_job_progress_age_seconds",
            "Seconds since the job's newest heartbeat — the staleness "
            "signal: a healthy steps/sec with a growing age means the "
            "workload stopped reporting (hung), not that it is training",
        )
        # ---- flight-recorder surfaces (obs/): latency distributions ----
        # Counters/gauges above say WHAT happened; these histograms say
        # where the time went, live, with p50/p99 derivable per scrape.
        self.sync_pass_seconds = self.histogram(
            "tpujob_sync_pass_seconds",
            "Supervisor sync-pass latency by phase (serial scheduling vs "
            "steady — the parallel-pool phase the autoscaler drives — "
            "vs total)",
        )
        self.reconcile_seconds = self.histogram(
            "tpujob_reconcile_seconds",
            "Per-job reconcile duration (all jobs pooled — label-per-job "
            "would explode series cardinality at fleet scale)",
        )
        self.store_persist_seconds = self.histogram(
            "tpujob_store_persist_seconds",
            "JobStore persist latency per update (clean skips included — "
            "the O(1) dirty check IS the distribution's left edge)",
        )
        self.store_rescan_seconds = self.histogram(
            "tpujob_store_rescan_seconds",
            "JobStore rescan (scandir snapshot + marker scans) latency",
        )
        self.step_time_seconds = self.histogram(
            "tpujob_step_time_seconds",
            "Per-job training step time, folded from progress heartbeats "
            "(interval-averaged: 1/steps_per_sec per heartbeat)",
        )
        self.checkpoint_commit_seconds = self.histogram(
            "tpujob_checkpoint_commit_seconds",
            "Per-job async checkpoint commit duration, folded from "
            "checkpoint_committed status records",
        )
        self.rendezvous_join_seconds = self.histogram(
            "tpujob_rendezvous_join_seconds",
            "Worker rendezvous join duration, folded from rendezvous_join "
            "status records",
        )
        # Data-plane companion gauges for the fold (tpujob top columns).
        self.job_checkpoint_step = self.gauge(
            "tpujob_job_checkpoint_step",
            "Newest committed (sidecar-verified) checkpoint step per job — "
            "checkpoint lag = tpujob_job_step minus this",
        )
        self.job_ckpt_queue_depth = self.gauge(
            "tpujob_job_ckpt_queue_depth",
            "Async checkpoint writer queue depth at the newest commit",
        )
        self.job_ckpt_oldest_age = self.gauge(
            "tpujob_job_ckpt_oldest_inflight_age_seconds",
            "Age of the oldest in-flight async checkpoint at the newest "
            "commit",
        )
        self.job_ckpt_stage_depth = self.gauge(
            "tpujob_job_ckpt_stage_depth",
            "Staged-writer snapshot-stage depth at the newest commit "
            "(submitted saves whose device→host gather has not finished)",
        )
        # Live health engine (obs/watch.py): firing alerts per
        # job/rule/severity, rebuilt per pass from the watch state —
        # the scrapeable face of the alert lifecycle (pending alerts
        # are hysteresis-internal and deliberately not exported).
        self.alerts_firing = self.gauge(
            "tpujob_alerts",
            "Firing live-health alerts per job/rule/severity "
            "(obs/watch.py; pending/resolved states are not exported)",
        )
        # Auto-remediation (controller/remediation.py): one counter
        # bump per audit record (dry-run included — the outcome label
        # separates them), plus last-action / generation gauges so a
        # dashboard shows "what did the engine last do and when".
        self.remediations_total = self.counter(
            "tpujob_remediations_total",
            "Remediation actions per job/rule/action/outcome "
            "(controller/remediation.py; outcome=dry_run means audited "
            "but not actuated)",
        )
        self.remediation_last = self.gauge(
            "tpujob_remediation_last_action",
            "Unix time of the last remediation action per "
            "job/rule/action",
        )
        self.remediation_generation = self.gauge(
            "tpujob_remediation_generation",
            "Committed remediation generation per job (the lifetime "
            "action count, lease-fenced through the store)",
        )
        # ---- sharded control plane (controller/leases.py) ----
        self.shard_jobs = self.gauge(
            "tpujob_shard_jobs",
            "Unfinished jobs per owned shard, labeled with the owning "
            "supervisor identity — rebuilt per pass; the fleet view is "
            "the union across every supervisor's /metrics",
        )
        self.supervisor_pass_seconds = self.gauge(
            "tpujob_supervisor_pass_seconds",
            "This supervisor's last full sync-pass latency (per-daemon "
            "gauge; the pooled distribution is tpujob_sync_pass_seconds)",
        )
        self.shards_owned = self.gauge(
            "tpujob_shards_owned",
            "Shard leases this supervisor currently holds (0 when the "
            "control plane runs unsharded)",
        )
        self.shard_acquisitions = self.counter(
            "tpujob_shard_acquisitions_total",
            "Shard leases acquired (bootstrap, takeover after expiry, "
            "rebalance claim)",
        )
        self.shard_releases = self.counter(
            "tpujob_shard_releases_total",
            "Shard leases voluntarily released (rebalance on member "
            "join, drain)",
        )
        self.shard_losses = self.counter(
            "tpujob_shard_losses_total",
            "Shard leases LOST: renewal fencing-rejected (a newer owner "
            "took over) or expired before renewal",
        )
        self.shard_guard_skips = self.counter(
            "tpujob_shard_guard_skips_total",
            "Reconciles refused because the shard lease was no longer "
            "valid at admission — each one is a double reconcile that "
            "did not happen",
        )
        # ---- steady-pool autoscaler (controller/autoscale.py) ----
        self.sync_pool_size = self.gauge(
            "tpujob_sync_pool_size",
            "Current steady-phase reconcile pool size (latency-driven "
            "autoscaler; floor on an idle fleet)",
        )
        self.sync_pool_max = self.gauge(
            "tpujob_sync_pool_max",
            "Configured steady-phase pool ceiling (--sync-workers-max)",
        )
        self.steady_fast_skips = self.counter(
            "tpujob_steady_fast_skips_total",
            "Steady jobs whose full reconcile was skipped because "
            "nothing changed since the last pass (replica set, job "
            "generation, and status files all unchanged)",
        )
        self.job_feed_stall = self.gauge(
            "tpujob_job_feed_stall_ms",
            "Mean step-loop wait on the device feed per get (0 = the feed "
            "thread keeps ahead), as reported in progress heartbeats",
        )
        # ---- serve plane (serving/router.py) ----
        # Folded per pass for serving jobs only; a fleet with no
        # serving jobs never creates a single serve series (the
        # bench_smoke zero-overhead pin).
        self.job_serve_queue_depth = self.gauge(
            "tpujob_job_serve_queue_depth",
            "Front-queue depth per serving job (unclaimed + undispatched "
            "requests ahead of admission)",
        )
        self.job_serve_inflight = self.gauge(
            "tpujob_job_serve_inflight",
            "Requests admitted and in flight through the router per "
            "serving job",
        )
        self.job_serve_replicas = self.gauge(
            "tpujob_job_serve_replicas",
            "Alive serving replicas the router can dispatch to, per job",
        )
        self.job_serve_slots_free = self.gauge(
            "tpujob_job_serve_slots_free",
            "Free decode slots summed across a serving job's replicas "
            "(from serve telemetry records)",
        )
        self.serve_requests = self.counter(
            "tpujob_serve_requests_total",
            "Responses the router published, per job and outcome "
            "(ok / shed / error)",
        )
        self.serve_rerouted = self.counter(
            "tpujob_serve_rerouted_total",
            "Requests re-enqueued to another replica after a replica "
            "death, per job",
        )
        self.serve_ttft_seconds = self.histogram(
            "tpujob_serve_ttft_seconds",
            "Client-perceived time to first token per serving job "
            "(submit -> first token, queue wait included), with request "
            "exemplars",
        )
        self.serve_tpot_seconds = self.histogram(
            "tpujob_serve_tpot_seconds",
            "Per-output-token decode latency per serving job",
        )
        self.serve_queue_wait_seconds = self.histogram(
            "tpujob_serve_queue_wait_seconds",
            "Front-queue wait per request (submit -> dispatch to a "
            "replica spool)",
        )
        self.slo_burn_rate = self.gauge(
            "tpujob_slo_burn_rate",
            "Error-budget burn rate per serving job and rolling window "
            "(serving/slo.py BurnAccount: bad fraction / (1 - target); "
            "1.0 = spending budget exactly as fast as the SLO earns it)",
        )
        # Live mirrors of the bench-only I/O instrumentation: idle-I/O
        # regressions become visible in production, not just in the
        # control-plane bench (store deltas folded once per pass).
        self.store_io = {
            k: self.counter(
                f"tpujob_store_{k}_total",
                f"JobStore persistence-layer {k.replace('_', ' ')} "
                "(StoreIOCounters, folded per sync pass)",
            )
            for k in ("reads", "writes", "writes_skipped", "scans",
                      "serializations")
        }
        self.progress_io = {
            k: self.counter(
                f"tpujob_progress_{k}_total",
                f"Progress-heartbeat tailer {k.replace('_', ' ')} "
                "(ProgressTailer fold stats, folded per sync pass)",
            )
            for k in ("dir_scans", "file_reads", "bytes_read")
        }
        self.router_io = {
            k: self.counter(
                f"tpujob_serve_router_{k}_total",
                f"Serve-plane router {k.replace('_', ' ')} "
                "(RouterIOCounters, folded per sync pass; all zero "
                "when no serving jobs exist)",
            )
            for k in ("ticks", "front_scans", "dispatches", "publishes",
                      "sweeps", "ring_sends", "ring_recvs", "ring_spills",
                      "shard_passes")
        }
        # Per-LANE router counters (labeled lane=<index>): the job-sum
        # family above answers "how much"; these answer "which lane" —
        # a single hot lane or a lane stuck spilling ring→file is
        # invisible in the sums.
        self.router_lane_io = {
            k: self.counter(
                f"tpujob_router_{k}_total",
                f"Serve-plane router {k.replace('_', ' ')} per lane "
                "(ServeRouter.lane_io_snapshot deltas, folded per sync "
                "pass; lane label is the shard index)",
            )
            for k in ("ring_sends", "ring_recvs", "ring_spills",
                      "shard_passes")
        }

    def counter(self, name: str, help_text: str = "") -> Counter:
        if name not in self._counters:
            self._counters[name] = Counter(name, help_text)
        return self._counters[name]

    def gauge(self, name: str, help_text: str = "") -> Gauge:
        if name not in self._gauges:
            self._gauges[name] = Gauge(name, help_text)
        return self._gauges[name]

    def histogram(self, name: str, help_text: str = "", buckets=None):
        """Register (or fetch) a Histogram (obs/metrics.py — imported
        lazily: obs depends on this module for label escaping)."""
        if name not in self._histograms:
            from ..obs.metrics import Histogram

            self._histograms[name] = Histogram(name, help_text, buckets)
        return self._histograms[name]

    def retire_job(self, key: str) -> int:
        """Metric lifecycle: drop every ``job=<key>`` series — histogram
        buckets AND gauges — from the live registry. Called when a job
        is deleted (reconciler/TTL GC, ``tpujob delete``): per-job
        series are label-cardinality a supervisor pays FOREVER otherwise
        (the ROADMAP unbounded-cardinality item — fine for thousands of
        jobs, fatal for millions). Finished-but-undeleted jobs keep
        their series: they are the postmortem surface ``tpujob why``
        reads. Returns the number of series dropped."""
        dropped = 0
        for h in self._histograms.values():
            dropped += h.drop_series("job", key)
        for g in self._gauges.values():
            dropped += g.drop_series("job", key)
        return dropped

    def series_count(self) -> int:
        """Total live labeled series across all families — the bound
        the churn test pins."""
        n = 0
        for h in self._histograms.values():
            n += h.series_count()
        for g in self._gauges.values():
            n += g.series_count()
        return n

    def render_text(self) -> str:
        parts = [c.render() for c in self._counters.values()]
        parts += [g.render() for g in self._gauges.values()]
        parts += [h.render() for h in self._histograms.values()]
        return "\n".join(parts) + "\n"
