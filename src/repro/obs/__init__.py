"""Engine-wide observability: metrics + span tracing (DESIGN.md §16).

One :class:`Observability` object bundles a
:class:`~repro.obs.metrics.MetricsRegistry` and a
:class:`~repro.obs.trace.SpanTracer`.  Instrumented code takes an
``obs`` handle and uses it unconditionally::

    obs.counter("engine_steps_total").inc()
    with obs.span("engine.step"):
        ...

When observability is off the handle is :data:`NOOP` — a process-global
disabled instance whose registry/tracer are shared null objects, so the
instrumented line above costs two trivial method calls and nothing else.
Hot paths that must also skip ``time.perf_counter()`` calls guard on
``obs.enabled``.

:func:`watch_jax_compiles` feeds a registry from JAX's own compile events
(:data:`JAX_EVENTS`), so it says how many seconds the process spent
tracing, lowering and backend compiling.  The listeners are process-wide;
the layer that owns the process's engine builds
(:class:`~repro.service.scheduler.DiscoveryService`) registers them.
"""
from __future__ import annotations

import threading
from typing import Callable

import jax

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_METRIC, NULL_REGISTRY, log_buckets)
from repro.obs.trace import NULL_SPAN, NULL_TRACER, SpanTracer

#: JAX compile event -> seconds counter
JAX_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_seconds_total",
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        "jax_lower_seconds_total",
    "/jax/core/compile/backend_compile_duration":
        "jax_backend_compile_seconds_total",
}


def watch_jax_compiles(metrics: MetricsRegistry) -> Callable[[], None]:
    """Count the seconds of JAX's compile events (all of the process's)
    into ``metrics``; returns the function that stops counting.

    An event counts only if no other event of its kind encloses it on its
    thread: a jit called while its caller is traced reports a trace of
    its own inside the caller's, and adding both would count that time
    twice.  JAX marks an event's start with a scalar (its start time) and
    its end with a time span."""
    seconds = {event: metrics.counter(name, f"seconds in {event}")
               for event, name in JAX_EVENTS.items()}
    local = threading.local()

    def depths() -> dict:
        if not hasattr(local, "depth"):
            local.depth = dict.fromkeys(JAX_EVENTS, 0)
        return local.depth

    def on_start(event, _value, **_kw):
        if event in seconds:
            depths()[event] += 1

    def on_span(event, start, end, **_kw):
        counter = seconds.get(event)
        if counter is None:
            return
        depth = depths()
        depth[event] = max(0, depth[event] - 1)
        if depth[event] == 0:
            counter.inc(max(0.0, end - start))

    def unwatch() -> None:
        jax.monitoring.unregister_scalar_listener(on_start)
        jax.monitoring.unregister_event_time_span_listener(on_span)

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_time_span_listener(on_span)
    return unwatch


class Observability:
    """Metrics registry + span tracer behind one enable switch."""

    def __init__(self, enabled: bool = True, max_spans: int = 1 << 16):
        self.enabled = enabled
        if enabled:
            self.metrics = MetricsRegistry()
            self.tracer = SpanTracer(capacity=max_spans)
        else:
            self.metrics = NULL_REGISTRY
            self.tracer = NULL_TRACER

    # convenience pass-throughs so call sites read `obs.counter(...)`
    def counter(self, name: str, help: str = ""):
        return self.metrics.counter(name, help)

    def gauge(self, name: str, help: str = ""):
        return self.metrics.gauge(name, help)

    def histogram(self, name: str, help: str = "", buckets=None):
        return self.metrics.histogram(name, help, buckets=buckets)

    def span(self, name: str, **meta):
        if not self.enabled:
            return NULL_SPAN
        return self.tracer.span(name, **meta)

    def snapshot(self) -> dict:
        """JSON-serializable state: all metrics + tracer occupancy."""
        return {
            "enabled": self.enabled,
            "metrics": self.metrics.snapshot(),
            "spans": {"recorded": self.tracer.total_recorded,
                      "dropped": self.tracer.dropped,
                      "capacity": self.tracer.capacity},
        }


#: process-global disabled instance — the default ``obs`` everywhere
NOOP = Observability(enabled=False)

__all__ = [
    "Observability", "NOOP", "JAX_EVENTS", "watch_jax_compiles",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "log_buckets",
    "NULL_METRIC", "NULL_REGISTRY",
    "SpanTracer", "NULL_TRACER", "NULL_SPAN",
]
