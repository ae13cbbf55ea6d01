"""Unified engine observability (ISSUE 9).

Three pieces behind one namespace:

- **Span tracing** (``obs.span`` / ``obs.detailed_span``): nestable
  timed regions in lock-free per-thread ring buffers, toggled by
  ``CONFIG.tracing = "off" | "on" | "detailed"``.  Disabled mode is one
  branch and no allocation.  Recorded spans also enter a
  ``jax.profiler.TraceAnnotation``, so a profiler trace shows them on
  the device's clock; ``obs.trace_scope(query_id)`` tags the spans of
  one query across threads (``obs.bind`` carries it into a thread).
- **Metrics** (``obs.metrics``): a central registry absorbing every
  layer's STATS object (``core.join`` / ``sql.compile`` /
  ``core.pipeline`` / ``serve`` / ``store.pool`` + ``store.spill`` /
  ``device`` / ``frame`` — the legacy names stay valid aliases) plus
  native counters, with ``snapshot()`` / ``reset()`` / ``diff()``.
- **Exporters**: ``obs.export_chrome_trace(path)`` (open in Perfetto /
  chrome://tracing) and ``obs.export_json()`` (operator-time breakdown
  + metrics snapshot; the bench runner attaches it to every row).

``EXPLAIN ANALYZE`` (``repro.sql.execute(..., explain="analyze")``)
builds on the tracer: see ``repro.sql.analyze``.

Import-time constraint (CI-enforced): ``import repro.obs`` must not
initialize jax — engine layers register their metrics groups when they
import, and ``obs.metrics.load_engine_groups()`` pulls them all in
explicitly.
"""
from . import metrics  # noqa: F401  (module-as-namespace)
from .export import aggregate_operators, export_chrome_trace, export_json
from .trace import (
    SpanRecord,
    annotate,
    bind,
    clear as clear_trace,
    current_span_id,
    detailed,
    detailed_span,
    dropped,
    enabled,
    mark_ns,
    span,
    spans,
    trace_scope,
)

__all__ = [
    "SpanRecord",
    "aggregate_operators",
    "annotate",
    "bind",
    "clear_trace",
    "current_span_id",
    "detailed",
    "detailed_span",
    "dropped",
    "enabled",
    "export_chrome_trace",
    "export_json",
    "mark_ns",
    "metrics",
    "span",
    "spans",
    "trace_scope",
]
