"""Cross-layer flight recorder: span tracing + latency histograms.

The control and data planes got fast (PRs 2-3) but only offline bench
JSON proves it; this package makes the LIVE system debuggable. Two
primitives, deliberately tiny and import-light (the step loop and the
supervisor's per-job reconcile both touch them every iteration):

- :class:`~pytorch_operator_tpu.obs.metrics.Histogram` — fixed
  log-spaced buckets, Prometheus text exposition alongside the existing
  Counter/Gauge (controller/metrics.py registers them; ``/metrics``
  serves step-time, reconcile-pass, and checkpoint-commit
  distributions, not just point gauges).
- :class:`~pytorch_operator_tpu.obs.trace.SpanRecorder` — buffers
  ``{name, cat, ts, dur, pid, tid, id, parent, args}`` span records in
  memory and appends them to a per-process JSONL ring file under
  ``$TPUJOB_TRACE_DIR`` at :func:`flush` (a heartbeat, a report, the
  supervisor's pass, exit), never inside a span's exit. A span is one
  interval of one layer's work — a supervisor pass, a reconcile, a
  training ``step``, a checkpoint commit, a feed batch, the serving
  loop's ``serve.poll`` / ``serve.respond`` and the engine's
  ``engine.step`` with its parts, a request's hop — named by the layer,
  numbered (``id``) and pointing at the span open around it
  (``parent``), so a layer's self time is its span less its children.
  The module helpers (:func:`span`, :func:`tracer`) are ZERO-overhead
  when the env knob is unset: one cached None check, a shared
  nullcontext, no I/O.
- The same :func:`span` is mirrored into ``jax.profiler`` as a
  ``TraceAnnotation`` of the same name and arguments while a profiler
  session records (``--profile-dir``, the benchmark's ``--trace 1``),
  so the program's spans and the device's operations share one clock.
  The package never imports JAX: it finds it in ``sys.modules``.

``tpujob trace <job>`` merges the supervisor's and every replica's span
files into one Chrome-trace/Perfetto JSON (:func:`merge_trace_files`),
clock-aligning cross-host files via the heartbeat-matched offset
estimator (obs/clock.py), and prints each span name's self time from
the ``parent`` links (``obs.trace.span_self_times``); ``tpujob top`` renders the live fleet table
from ``/metrics`` + progress heartbeats (obs/top.py); ``tpujob why``
runs the offline postmortem — causal timeline + anomaly detectors —
over the recorded artifacts (obs/analyze.py).
"""

from .metrics import (
    DEFAULT_BUCKETS,
    Histogram,
    histogram_quantile,
    parse_exemplars,
    parse_prometheus_text,
)
from .trace import (
    SERVE_CAT,
    SpanRecorder,
    flush,
    instant,
    load_span_file,
    merge_trace_files,
    records_emitted,
    reset_tracer,
    serve_span,
    span,
    trace_enabled,
    tracer,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "Histogram",
    "SERVE_CAT",
    "SpanRecorder",
    "flush",
    "histogram_quantile",
    "instant",
    "load_span_file",
    "merge_trace_files",
    "parse_exemplars",
    "parse_prometheus_text",
    "records_emitted",
    "reset_tracer",
    "serve_span",
    "span",
    "trace_enabled",
    "tracer",
]
