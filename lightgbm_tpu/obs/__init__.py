"""Observability subsystem: metrics registry, run reports, profiling.

One span API: ``obs.trace.span`` (``utils/timing.phase`` is the same
thing plus ``.watch``). A span records name, start, end, thread, the
enclosing span of its thread (``parent``) and the span that handed its
work over from another thread (``cause``); it always adds to the
registry timer of its name, and it is a
``jax.profiler.TraceAnnotation("lgbm/<name>")`` inside ANY open profiler
session — a benchmark's, ``tpu_profile_dir``'s, an operator's — with
nothing to switch on.

- ``obs.registry`` — thread-safe counters/gauges/histograms/timers; the
  phase accounting in utils/timing.py stores here, the ingest pipeline
  counts transfer bytes here (io/ingest.py), and everything lands in
  the run report.
- ``obs.recorder`` — per-iteration RunRecorder + the versioned
  JSON/JSONL run-report artifact (config ``tpu_run_report``), the
  slow-iteration watchdog (``tpu_watchdog_factor``), and the
  ``[t+12.3s it=140]`` log prefix.
- ``obs.profiler`` — the ``tpu_profile_dir``/``tpu_profile_iters``
  iteration-window bracket round ``jax.profiler.start_trace``/
  ``stop_trace``.
- ``obs.trace`` — the span site, and the cross-thread tracer (config
  ``tpu_trace``/``tpu_trace_buffer``): ring-buffered Chrome trace-event
  JSON showing the ingest worker, the training iterations, step-cache
  compiles and the lrb window phases on one Perfetto timeline.
- ``obs.export`` — live metrics exporter (``tpu_metrics_export``/
  ``tpu_metrics_interval_s``/``tpu_metrics_port``): a daemon that
  snapshots the default registry to Prometheus text + JSONL on an
  interval and serves ``/metrics`` + the operational ``/healthz`` and
  ``/slo`` endpoints over HTTP during a run.
- ``obs.reqlog`` — request-scoped wide events (``tpu_reqlog``/
  ``tpu_reqlog_sample``): monotonically-issued request ids carried
  through the predict stack in a thread-local context, one structured
  JSONL record per request batch and per lrb window, deterministic
  per-id file sampling, and an always-on ring the flight recorder
  dumps.
- ``obs.slo`` — SLO / error-budget engine (``tpu_slo``): declarative
  objective specs evaluated by the exporter thread every interval;
  compliance, remaining error budget and burn rate become first-class
  gauges and the ``/healthz``/``/slo`` bodies.
- ``obs.scopes`` — ``op_scopes()``: each instruction name of the
  compiled steps (and the stop check's download) to the innermost
  ``lgbm/`` scope it runs under, built on the first ask from the
  abstract signature the step cache keeps at first dispatch; a reader
  joins it with a profiler trace's device self time by op name.
- ``obs.flight`` — flight recorder (``tpu_flight_buffer``): always-on
  bounded rings of recent spans, log lines, reqlog records and metric
  snapshots, dumped as ONE self-contained postmortem bundle on
  watchdog firings, faults, degraded lrb windows, SLO budget
  exhaustion, SIGTERM and uncaught exceptions; run reports cross-link
  the dumps as ``meta.flight_dumps``.

Only the stdlib-dependency modules (registry, trace, export, reqlog,
slo, flight, scopes) are imported eagerly (utils/timing.py depends on
registry and trace at module load; scopes imports jax inside its
functions); recorder/profiler import jax-adjacent modules and load on
first use.
"""
from . import export, flight, registry, reqlog, scopes, slo, trace
from .registry import (MetricsRegistry, counter, default_registry, gauge,
                       histogram, latency_histogram, timer)
from .scopes import op_scopes

__all__ = [
    "registry", "trace", "export", "reqlog", "slo", "flight", "scopes",
    "MetricsRegistry", "default_registry", "counter", "gauge",
    "histogram", "latency_histogram", "timer", "op_scopes",
]
