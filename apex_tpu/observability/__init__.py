"""apex_tpu.observability — dependency-free metrics + tracing.

The reference apex ships its subsystems dark: loss-scale decisions,
fused-optimizer behavior, and collective traffic are invisible without
user prints.  This package is the one measurement path for the repo —
``bench.py``, ``tools/measure_all.py`` and the training loops all
report through it — built from three pieces:

- :mod:`apex_tpu.observability.metrics` — a process-local registry of
  counters, gauges and histogram/quantile summaries, tagged with the
  same rank sources as ``utils/logging.RankInfoFormatter``, with
  pluggable sinks (JSONL file, stderr summary) and a module-level
  **no-op fast path**: when telemetry is not configured every
  instrumented call site costs one ``is None`` check.
- :mod:`apex_tpu.observability.spans` — ``with span("fwd")`` (context
  manager + decorator) and :class:`StepTimer`, the step-timing
  protocol (warmup fenced per-iteration, one trailing fence across the
  timed iterations) with a scalar-materialization fence.
- :mod:`apex_tpu.observability.sinks` — the JSONL and stderr-summary
  sinks; the ``jax.profiler`` trace-annotation sink is the
  ``profiler=True`` feature flag (``APEX_TPU_TELEMETRY_PROFILER=1``),
  consumed by :mod:`~apex_tpu.observability.spans`.

The flight-recorder & diagnostics layer (ISSUE 4) builds on those:

- :mod:`apex_tpu.observability.trace` — Chrome trace_events / Perfetto
  export of the whole record stream (``configure(trace_path=...)`` /
  ``APEX_TPU_TELEMETRY_TRACE``): spans as slices, gauges/counters as
  counter tracks, serving requests as async rows.
- :mod:`apex_tpu.observability.recorder` — the flight recorder: a
  bounded ring of the last N steps' scalars dumped as a JSON
  post-mortem on crash, on first anomaly, or on demand
  (``configure(flight_recorder="flight.json")`` /
  ``APEX_TPU_TELEMETRY_FLIGHT``; render with tools/health_report.py).
- :mod:`apex_tpu.observability.detectors` — step-boundary anomaly
  detectors (loss-spike, grad-norm explosion, NaN/Inf first-seen,
  scaler thrash, throughput regression, serving queue stalls), fed
  automatically by ``record_step_metrics`` / ``record_scaler_step`` /
  span observations.
- :mod:`apex_tpu.observability.device` — runtime accounting: the
  ``jax.monitoring``-based recompilation tracker
  (``compile.{count,ms}`` per :func:`compile_label`) and HBM gauges
  from ``device.memory_stats()`` (``hbm.{bytes_in_use,peak_bytes}``),
  attached to BENCH JSON by ``bench.py``.

Everything is host-side at step boundaries: no host callbacks, nothing
traced into jit bodies — device values enter telemetry only through the
aux/metrics values a step already returns.  See docs/observability.md.
"""

from apex_tpu.observability.device import (  # noqa: F401
    compile_label,
    install_recompile_tracker,
    recompile_tracker,
    runtime_summary,
    sample_device_memory,
)
from apex_tpu.observability.metrics import (  # noqa: F401
    SCHEMA_VERSION,
    MetricsRegistry,
    configure,
    configure_from_env,
    counter,
    enabled,
    event,
    gauge,
    histogram,
    record_step_metrics,
    registry,
    set_step,
    shutdown,
    sketch,
)
from apex_tpu.observability.sketches import LogBucketSketch  # noqa: F401
from apex_tpu.observability.recorder import FlightRecorder  # noqa: F401
from apex_tpu.observability.sinks import JsonlSink, StderrSummarySink  # noqa: F401
from apex_tpu.observability.spans import StepTimer, fence, span  # noqa: F401
from apex_tpu.observability.trace import TraceSink, load_trace  # noqa: F401

__all__ = [
    "SCHEMA_VERSION",
    "FlightRecorder",
    "LogBucketSketch",
    "MetricsRegistry",
    "JsonlSink",
    "StderrSummarySink",
    "StepTimer",
    "TraceSink",
    "compile_label",
    "configure",
    "configure_from_env",
    "counter",
    "enabled",
    "event",
    "fence",
    "gauge",
    "histogram",
    "install_recompile_tracker",
    "load_trace",
    "recompile_tracker",
    "record_step_metrics",
    "registry",
    "runtime_summary",
    "sample_device_memory",
    "set_step",
    "shutdown",
    "sketch",
    "span",
]
