"""Observability primitives shared by every layer of the system.

This package is dependency-neutral (stdlib + numpy only at import
time), so the core index, the kernels, the distributed tier, and the
serving tier can all instrument themselves against ONE registry and
ONE span format without import cycles:

  registry.py  named counters / gauges / histograms in a
               ``MetricsRegistry`` with a stable snapshot export
               (JSON + Prometheus text, both round-trippable), the
               process-global ``GLOBAL`` registry engine-level counters
               land in, and the bounded structured ``EventLog`` the
               index maintenance path emits into
  trace.py     query tracing — monotonic-clock ``Span``/``Trace``
               threaded through the serving read path, a sampling
               ``Tracer`` (zero span construction when disabled), and
               the ``StageAggregator`` that folds per-request stage
               durations into registry histograms; ``stage`` opens a
               span that is also a profiler annotation (``serve.*``)
"""
from repro.obs.registry import (GLOBAL, Counter, EventLog, Gauge, Histogram,
                                MetricsRegistry, global_registry,
                                parse_prometheus, snapshot_from_json,
                                snapshot_to_json)
from repro.obs.trace import Span, StageAggregator, Trace, Tracer, stage

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "EventLog",
    "GLOBAL", "global_registry", "parse_prometheus", "snapshot_to_json",
    "snapshot_from_json", "Span", "Trace", "Tracer", "StageAggregator",
    "stage",
]
