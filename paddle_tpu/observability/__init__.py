"""Always-on runtime telemetry.

What the reference stack spread across ``fluid.profiler`` (opt-in
sessions), VisualDL (scalar logging) and ad-hoc prints, collapsed into
one low-overhead layer that is simply *on*:

* :mod:`.metrics` — process-wide registry of counters / gauges /
  fixed-bucket histograms; ``PADDLE_TPU_TELEMETRY=0`` kill switch;
* :mod:`.journal` — schema-versioned step/event ring buffer, flushed
  as JSONL into ``PADDLE_TPU_TELEMETRY_DIR`` for the monitor CLI;
* :mod:`.drift` — predicted-vs-measured drift gauges joining the
  static cost model against measured step latencies, feeding
  calibration factors back into the autotune cache continuously;
* :mod:`.exporters` — Prometheus text, JSON snapshot, merged
  host+device chrome trace;
* :mod:`.runtime` — the one-line hooks the executor, async pipeline,
  resilience runtime and fusion resolver call;
* :mod:`.tracing` — distributed spans (cross-thread / cross-process
  context propagation, ``PADDLE_TPU_TRACING=0`` kill switch) plus a
  flight recorder dumped on fatal conditions.

Tail a live run with ``python -m paddle_tpu.tools.monitor <dir>``;
reconstruct traces with ``python -m paddle_tpu.tools.trace <dir>``.
"""

from . import drift, exporters, journal, metrics, runtime, tracing  # noqa: F401
from .drift import (DRIFT_CALIBRATION_FAMILY, DriftMonitor,
                    ProgramDrift, monitor, program_key, reset_drift)
from .exporters import (export_json, export_prometheus,
                        write_chrome_trace, write_metrics_snapshot)
from .journal import (SCHEMA_VERSION, Journal, emit, get_journal,
                      journal_dir, read_journal, reset_journal)
from .metrics import (DEFAULT_LATENCY_BUCKETS_MS, Counter, Gauge,
                      Histogram, MetricsRegistry, counter, gauge,
                      histogram, registry, reset_metrics,
                      set_telemetry_enabled, telemetry_enabled)
from .tracing import (NULL_SPAN, Span, SpanContext, Tracer,
                      capture_context, current_span, current_trace_id,
                      current_traceparent, flight_dump, get_tracer, phase,
                      read_flight_records, read_traces, reset_tracing,
                      sample_step, set_rank, set_tracing_enabled, span,
                      start_span, step_sample_every, tracing_enabled,
                      use_context)

__all__ = [
    # metrics
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "DEFAULT_LATENCY_BUCKETS_MS", "registry", "counter", "gauge",
    "histogram", "telemetry_enabled", "set_telemetry_enabled",
    "reset_metrics",
    # journal
    "SCHEMA_VERSION", "Journal", "get_journal", "emit", "read_journal",
    "journal_dir", "reset_journal",
    # drift
    "DRIFT_CALIBRATION_FAMILY", "DriftMonitor", "ProgramDrift",
    "monitor", "program_key", "reset_drift",
    # exporters
    "export_prometheus", "export_json", "write_metrics_snapshot",
    "write_chrome_trace",
    # tracing
    "Span", "SpanContext", "Tracer", "NULL_SPAN", "span", "start_span",
    "phase", "sample_step", "step_sample_every",
    "current_span", "current_trace_id", "current_traceparent",
    "capture_context", "use_context", "get_tracer", "flight_dump",
    "read_traces", "read_flight_records", "tracing_enabled",
    "set_tracing_enabled", "set_rank", "reset_tracing",
]


def reset_telemetry():
    """Full reset — metrics, journal singleton, drift monitor, runtime
    cross-step state, tracer singleton (test isolation)."""
    reset_metrics()
    reset_journal()
    reset_drift()
    reset_tracing()
    runtime.reset_runtime()


__all__.append("reset_telemetry")
