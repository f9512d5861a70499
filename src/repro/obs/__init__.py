"""repro.obs — observability substrate for the serving runtime.

Three pieces, documented in ``docs/observability.md``:

  * :mod:`repro.obs.trace` — :class:`Tracer`, a thread-safe bounded
    ring-buffer span recorder with Chrome-trace / JSONL export, whose
    scoped spans also reach a recording ``jax.profiler`` session and
    :func:`span_totals`;
  * :mod:`repro.obs.series` — :class:`BoundedSeries`, capped-memory metric
    series with exact-then-bucketed percentiles;
  * :mod:`repro.obs.telemetry` / :mod:`repro.obs.prom` — per-bucket I/O
    gauges from the compiled plans and Prometheus text exposition.
"""

from .series import BoundedSeries
from .telemetry import IOTelemetry, plan_io_attrs
from .trace import (NULL_TRACER, Span, Tracer, reset_span_totals,
                    span_totals)
from .prom import MetricsServer, render_prometheus

__all__ = [
    "BoundedSeries",
    "IOTelemetry",
    "plan_io_attrs",
    "NULL_TRACER",
    "Span",
    "Tracer",
    "span_totals",
    "reset_span_totals",
    "MetricsServer",
    "render_prometheus",
]
