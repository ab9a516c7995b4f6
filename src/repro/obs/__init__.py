"""TopoScope + TopoWatch: tracing, metrics, SLOs, and serving health.

Passive layers (TopoScope, see ARCHITECTURE.md §TopoScope):

- **Metrics registry** (:mod:`repro.obs.metrics`) — process-wide
  thread-safe counters/gauges/histograms, always live; the serving
  frontends' ``stats`` surfaces are views over it.
- **Tracing** (:mod:`repro.obs.trace`) — nestable ``span()`` context
  managers producing Perfetto-loadable Chrome-trace JSON; off by
  default, enabled via ``REPRO_OBS=1`` or ``obs.configure(enabled=True)``.
- **Export + report** (:mod:`repro.obs.export`,
  :mod:`repro.obs.report`) — Prometheus text / JSON-lines snapshots and
  the ``python -m repro.obs report`` self-time table with roofline
  cost-cell attribution.

Active layers (TopoWatch, see ARCHITECTURE.md §TopoWatch):

- **Request context** (:mod:`repro.obs.context`) — contextvars-scoped
  request ids + absolute deadlines, minted by every ``submit()``,
  propagated into spans and futures; drains sweep expired requests with
  :class:`DeadlineExceeded` and skip cancelled ones.
- **SLO engine** (:mod:`repro.obs.slo`) — declarative latency/error/
  skip-rate/recall objectives evaluated by multi-window burn-rate rules
  over registry snapshots; ``python -m repro.obs watch`` / ``slo check``.
- **Scrape endpoints** (:mod:`repro.obs.http`) — dependency-free
  ``/metrics``, ``/healthz``, ``/readyz``, ``/varz``, ``/slo``,
  ``/debug/flight`` HTTP server.
- **Flight recorder** (:mod:`repro.obs.flight`) — always-on bounded
  ring of recent events, auto-dumped to ``results/obs/FLIGHT_<rev>.json``
  on SLO breach / deadline expiry / drain exception.

Typical instrumentation site::

    from repro import obs

    _CALLS = obs.counter("kernels.calls")

    def my_kernel(x):
        _CALLS.inc(kernel="my_kernel")
        with obs.span("kernels.my_kernel", shape=f"N{x.shape[0]}"):
            return _impl(x)
"""
from __future__ import annotations

from typing import Iterable, Optional

from .metrics import (
    Counter,
    DEFAULT_TIME_BUCKETS,
    Gauge,
    Histogram,
    MetricsRegistry,
    bucket_count_over,
    bucket_quantile,
    default_registry,
    next_instance,
)
from .context import (
    DeadlineExceeded,
    RequestContext,
    current_request_id,
    new_request_id,
    request_context,
)
from .trace import (
    Span,
    clear_trace,
    configure,
    current_span,
    dropped_events,
    enabled,
    export_chrome_trace,
    span,
    trace_events,
)
from .export import (
    append_jsonl,
    export_prometheus,
    prometheus_text,
    snapshot,
)
from .http import ObsHTTPServer, start_http_server
from .slo import (
    BurnRule,
    SLOEngine,
    SLOSpec,
    default_serve_slos,
    slo_status,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span",
    "DEFAULT_TIME_BUCKETS",
    "bucket_quantile", "bucket_count_over",
    "default_registry", "next_instance",
    "counter", "gauge", "histogram", "get_instrument",
    "configure", "enabled", "span", "current_span",
    "trace_events", "clear_trace", "dropped_events",
    "export_chrome_trace", "export_prometheus", "prometheus_text",
    "snapshot", "append_jsonl", "reset",
    # TopoWatch
    "DeadlineExceeded", "RequestContext", "request_context",
    "new_request_id", "current_request_id",
    "BurnRule", "SLOEngine", "SLOSpec", "default_serve_slos",
    "slo_status",
    "ObsHTTPServer", "start_http_server",
]


def counter(name: str, help: str = "") -> Counter:
    """Get-or-create a counter in the default registry."""
    return default_registry().counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return default_registry().gauge(name, help)


def histogram(name: str, help: str = "",
              buckets: Iterable[float] = DEFAULT_TIME_BUCKETS) -> Histogram:
    return default_registry().histogram(name, help, buckets=buckets)


def get_instrument(name: str):
    return default_registry().get(name)


def reset() -> None:
    """Zero every metric series, drop buffered trace events, and clear
    the flight-recorder ring.

    Instruments stay registered, so module-level references held by the
    instrumented subsystems keep recording.
    """
    from . import flight as _flight

    default_registry().reset()
    clear_trace()
    _flight.clear()
