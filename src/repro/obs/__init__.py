"""repro.obs — observability layer (docs/ARCHITECTURE.md §13).

One instrumentation vocabulary for the whole stack:

* ``obs.metrics`` — thread-safe counters / gauges / fixed-bucket
  histograms in per-``Service`` and process-``GLOBAL`` registries, with
  Prometheus text exposition and a module-level kill switch
  (``set_enabled(False)`` → every call site degrades to one branch).
* ``obs.trace`` — per-query span trees (parse→batch.wait→cache→plan→
  execute→device.wait→serialize) with wire-propagated trace ids, a
  bounded trace ring and a slow-query log; each stage is also a ``pg.``
  host event in a ``jax.profiler`` trace.
* ``obs.profile`` — EXPLAIN ANALYZE: executed plans annotated with
  per-stage wall times and the measured JAX compile-vs-execute split.
"""
from repro.obs.metrics import (
    GLOBAL,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    enabled,
    parse_prometheus,
    render_prometheus,
    set_enabled,
)
from repro.obs.profile import ProfileReport, profile_match
from repro.obs.trace import Span, Trace, TraceBuffer, new_trace_id

__all__ = [
    "GLOBAL",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "enabled",
    "parse_prometheus",
    "render_prometheus",
    "set_enabled",
    "ProfileReport",
    "profile_match",
    "Span",
    "Trace",
    "TraceBuffer",
    "new_trace_id",
]
