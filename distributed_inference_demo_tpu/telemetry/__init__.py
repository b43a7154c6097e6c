"""Unified telemetry layer: tracing, metrics, run logs, and the black box.

The integrated pieces (docs/DESIGN.md §7-§8):

- ``tracing``: per-request trace ids propagated across the ring via a
  wire flags bit (``comm/wire.py``), per-stage spans, Chrome trace-event
  export for Perfetto;
- ``metrics``: a hand-rolled Prometheus registry (no new dependency) +
  ``catalog``, the standard ``dwt_*`` series bridging StageStats,
  batching/speculative counters, and monitor probes to ``GET /metrics``;
- ``runlog``: structured JSONL run logs shared by the engines and the
  control-plane lifecycle;
- ``flightrecorder``: a bounded always-on ring of recent runtime events
  (the aircraft black box);
- ``anomaly``: online detectors over the existing stats surfaces
  (straggler hop, SLO breach, queue saturation, accept-rate collapse,
  stalled-pipeline watchdog);
- ``postmortem``: on trigger or crash, dump a bundle (flight ring,
  metrics snapshot, Chrome trace, config, run-log tail) for the offline
  analyzer ``tools/postmortem.py``.

``catalog`` is imported lazily by its consumers (it pulls in
monitor.probes); importing this package stays dependency-light so the
engine hot path can use ``runlog`` without dragging the control plane in.
"""

from .flightrecorder import (FlightRecorder, get_flight_recorder,
                             set_flight_recorder)
from .metrics import (Counter, Gauge, Histogram, MetricError,
                      MetricsHTTPServer, REGISTRY, Registry)
from .runlog import RunLog, get_run_log, new_run_id, set_run_log
from .tracing import (SpanClock, TraceRecorder, new_trace_id,
                      to_chrome_trace, write_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricError", "MetricsHTTPServer",
    "REGISTRY", "Registry",
    "RunLog", "get_run_log", "new_run_id", "set_run_log",
    "FlightRecorder", "get_flight_recorder", "set_flight_recorder",
    "SpanClock", "TraceRecorder", "new_trace_id", "to_chrome_trace",
    "write_chrome_trace",
]
