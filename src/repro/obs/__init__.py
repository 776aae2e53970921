"""repro.obs — session-wide observability (telemetry, traces, serve SLIs).

Three parts, one opt-in switch:

  telemetry  - ``GraphSession(telemetry=True | TelemetryConfig(...))``
               records a fixed-schema per-superstep ``TelemetrySeries``
               (returned on ``RunMetrics.telemetry``); on the device
               backend the series rides the scan carry, so a
               ``steps_per_sync=inf`` run still syncs exactly once.
  trace      - every session owns a ``TraceRecorder`` (``session.trace``)
               collecting submit/detach, superstep spans, apply_updates
               batches and compactions; ``session.trace.export(path)``
               writes Chrome/Perfetto trace-event JSON.  Whatever the
               switch, the program's spans (``trace.span``) are written to
               a live JAX profiler trace as ``repro.<name>`` annotations
               and summed, with its counters (``trace.count``), in
               ``trace.digest()``.
  serve      - ``ConcurrentServeScheduler.metrics`` records per-stream
               wait/service time and per-family queue depth with p50/p99
               summaries (the SLO signal of ROADMAP item 3).

On top of those, the serve-front SLO layer (ROADMAP item 3's production
half): ``loadgen`` generates deterministic open-loop arrival traffic and
drives a GraphSession + ConcurrentServeScheduler pair; ``slo`` tracks
sliding-window SLIs per family/tenant against declared ``SLOTarget``s and
snapshots every metrics source through a ``MetricsRegistry`` (JSON +
Prometheus text); ``python -m repro.obs.regress`` gates fresh benchmark
records against the committed BENCH_*.json trajectory.

Telemetry off (the default) compiles to the exact pre-observability
programs: the jitted superstep carries no buffers and fixpoints are
bitwise identical (pinned in tests/test_obs.py).
"""

from repro.obs.telemetry import (TelemetryConfig, TelemetrySeries,
                                 HostSeriesBuilder, device_buffers,
                                 device_write, series_from_device,
                                 SERIES_FIELDS, GROUP_FIELDS)
from repro.obs.trace import TraceRecorder, validate_trace_events
from repro.obs.serve import LatencyStats, ServeMetrics, percentile_summary
from repro.obs.slo import (SlidingWindowLatency, SLOTarget, SLOTracker,
                           MetricsRegistry, validate_registry_snapshot,
                           REGISTRY_SCHEMA)
from repro.obs.loadgen import (LoadgenConfig, Arrival, generate_arrivals,
                               OpenLoopHarness)

__all__ = [
    "TelemetryConfig", "TelemetrySeries", "HostSeriesBuilder",
    "device_buffers", "device_write", "series_from_device",
    "SERIES_FIELDS", "GROUP_FIELDS",
    "TraceRecorder", "validate_trace_events",
    "LatencyStats", "ServeMetrics", "percentile_summary",
    "SlidingWindowLatency", "SLOTarget", "SLOTracker",
    "MetricsRegistry", "validate_registry_snapshot", "REGISTRY_SCHEMA",
    "LoadgenConfig", "Arrival", "generate_arrivals", "OpenLoopHarness",
]
