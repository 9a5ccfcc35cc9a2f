"""Observability: metrics registry, span tracer, per-query explain plane.

Zero third-party imports at import time (the tracers import
``jax.profiler`` when one is built).  ``repro.obs`` imports nothing from the
rest of ``repro``, so any layer (data plane, engine, server, benches) can
depend on it without cycles.
"""

from repro.obs.explain import ExplainRecord, RoundSample
from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (
    NULL_TRACER,
    ROUND_SPAN,
    NullTracer,
    ProfilerTracer,
    SpanTracer,
    validate_chrome_trace,
)

__all__ = [
    "Counter",
    "ExplainRecord",
    "Gauge",
    "Histogram",
    "LATENCY_BUCKETS_S",
    "MetricsRegistry",
    "NULL_TRACER",
    "NullTracer",
    "ProfilerTracer",
    "ROUND_SPAN",
    "RoundSample",
    "SpanTracer",
    "validate_chrome_trace",
]
