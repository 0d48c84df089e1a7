"""Observability: flight-recorder tracing + process metrics (DESIGN.md §3.11).

Two small host-side pieces, imported by every instrumented subsystem
(``repro.obs`` deliberately imports nothing from the rest of the repo, and
no JAX at import — it must be safe to call from any layer, including module
import time; enabled tracing imports JAX to mark each span on the
``jax.profiler`` trace):

* :mod:`repro.obs.trace` — the span API and bounded ring buffer (flight
  recorder) with Perfetto/Chrome-trace export and dump-on-failure.
  **Disabled by default, zero-overhead when disabled.**
* :mod:`repro.obs.registry` — process-wide counter/gauge/histogram
  registry with a flat ``snapshot()`` and Prometheus text exposition.
  **Always on** (a lock + int add per bump).

The one-screen instrumentation idiom::

    from repro import obs

    with obs.span("engine.dispatch", dataset=name, measure=delta):
        result = jax.block_until_ready(runner(...))
    obs.counter("plar_engine_runs_total").inc()

Enable tracing with ``obs.enable()`` (or ``REPRO_TRACE=1``), export with
``obs.get_tracer().export("trace.json")``, read at https://ui.perfetto.dev.
"""
from .registry import (
    DEFAULT_BUCKETS,
    Counter,
    CounterMap,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    get_registry,
    histogram,
    render_prometheus,
)
from .trace import (
    SpanRecord,
    Tracer,
    disable,
    enable,
    event,
    get_tracer,
    request_dump,
    set_dump_dir,
    span,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "Counter",
    "CounterMap",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "SpanRecord",
    "Tracer",
    "counter",
    "disable",
    "enable",
    "event",
    "gauge",
    "get_registry",
    "get_tracer",
    "histogram",
    "render_prometheus",
    "request_dump",
    "set_dump_dir",
    "span",
]
