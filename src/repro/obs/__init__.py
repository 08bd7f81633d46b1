"""Observability layer: unified metrics registry, sampled per-query
tracing, the process-wide span ring, and Prometheus / Chrome-trace
exporters.

This package depends only on the standard library — it sits *below*
``repro.api`` / ``repro.serve`` in the import graph so any layer can
instrument itself without cycles.
"""
from .registry import (
    Counter,
    Gauge,
    Histogram,
    LATENCY_MS_BOUNDS,
    MetricsRegistry,
    log_bounds,
)
from .trace import (
    NOOP_SPAN, Span, Trace, Tracer, current, process_instruments, recorder,
    span,
)
from .export import (
    chrome_trace,
    dump_chrome_trace,
    json_snapshot,
    prometheus_text,
)
from .http import MetricsServer

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "LATENCY_MS_BOUNDS",
    "MetricsRegistry",
    "MetricsServer",
    "NOOP_SPAN",
    "Span",
    "Trace",
    "Tracer",
    "chrome_trace",
    "current",
    "dump_chrome_trace",
    "json_snapshot",
    "log_bounds",
    "process_instruments",
    "prometheus_text",
    "recorder",
    "span",
]
