"""Observability for the counting stack: tracing, percentiles, export.

Three pieces, each usable alone:

* :mod:`repro.obs.trace` — ring-buffered request tracing with a free
  no-op default (:data:`NULL_TRACER`) and the ``REPRO_TRACE`` env knob;
* :mod:`repro.obs.hist` — fixed-bucket log-scale latency histograms
  whose merge is exactly associative (p50/p95/p99 + max);
* :mod:`repro.obs.registry` — Prometheus-text / JSON rendering of
  snapshots, plus :mod:`repro.obs.slowlog` (top-K slow queries) and
  :mod:`repro.obs.profile` (``jax.profiler`` annotations: every live
  span is one while they are enabled).

This package deliberately imports nothing from :mod:`repro.core` or
:mod:`repro.serve`, so every layer of the stack can depend on it.
"""

from .hist import CountHistogram, LatencyHistogram, N_BUCKETS
from .profile import annotate
from .registry import MetricsRegistry, prometheus_lines
from .slowlog import SlowQuery, SlowQueryLog
from .trace import (NULL_TRACER, NullTracer, Span, SpanContext, SpanRecord,
                    Tracer, build_trees, default_tracer)

__all__ = [
    "CountHistogram", "LatencyHistogram", "N_BUCKETS",
    "annotate",
    "MetricsRegistry", "prometheus_lines",
    "SlowQuery", "SlowQueryLog",
    "NULL_TRACER", "NullTracer", "Span", "SpanContext", "SpanRecord",
    "Tracer", "build_trees", "default_tracer",
]
