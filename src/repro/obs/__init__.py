"""repro.obs — unified telemetry: tracing, metrics, audit, live health.

Five planes, one subsystem (docs/observability.md):

* :mod:`repro.obs.trace`   — per-window span tracing (:class:`Tracer`,
  off-by-default via :data:`NULL_TRACER`), Chrome-trace JSON export;
* :mod:`repro.obs.metrics` — the process-wide :data:`REGISTRY` of named
  counters/gauges/histograms (absorbs the legacy global counters), plus
  the compiled-program :func:`dispatch_count` launch counter and, in
  :mod:`repro.obs.host`, :func:`to_host`, the counted device->host
  transfer;
* :mod:`repro.obs.audit`   — the append-only security event stream owned
  by each :class:`repro.attest.KeyDirectory`;
* :mod:`repro.obs.monitor` — :class:`PipelineMonitor` sliding-window
  stage health + the SLO/stall :class:`Watchdog`;
* :mod:`repro.obs.export`  — Prometheus/JSON exporters and the stdlib
  HTTP scrape endpoint (:func:`serve_metrics`).
"""
from repro.obs.audit import AuditEvent, AuditLog
from repro.obs.export import (MetricsServer, prometheus_text, serve_metrics,
                              snapshot_json)
from repro.obs.host import to_host
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               REGISTRY, dispatch_count,
                               reset_dispatch_count)
from repro.obs.monitor import (Breach, NULL_MONITOR, NullMonitor,
                               PipelineMonitor, SLORule, Watchdog)
from repro.obs.trace import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "AuditEvent", "AuditLog",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "dispatch_count", "reset_dispatch_count", "to_host",
    "NULL_TRACER", "NullTracer", "Span", "Tracer",
    "Breach", "NULL_MONITOR", "NullMonitor", "PipelineMonitor",
    "SLORule", "Watchdog",
    "MetricsServer", "prometheus_text", "serve_metrics", "snapshot_json",
]
