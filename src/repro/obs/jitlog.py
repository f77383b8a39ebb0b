"""JAX tracing and compiles as program spans and counters.

JAX reports each trace to a jaxpr, each lowering to an MLIR module and
each backend compile (a persistent-cache load included) through
``jax.monitoring`` once it ends, on the thread that did the work.  One
listener per process maps those events to complete (``X``) events on
every attached sink's tracer:

====================================================  ===============
JAX event                                             span
====================================================  ===============
``/jax/core/compile/jaxpr_trace_duration``            ``jit.trace``
``/jax/core/compile/jaxpr_to_mlir_module_duration``   ``jit.lower``
``/jax/core/compile/backend_compile_duration``        ``jit.compile``
====================================================  ===============

Each span carries ``fun=<fun_name>``.  JAX stamps the event with
``time.time()``; the span ends at ``perf_counter()`` when the listener
runs and keeps the event's duration, so it lies on the tracer's clock.
Eager dispatch of a primitive on a new shape traces too, and a traced
function's inner primitives report nested ``jit.trace`` events: the
union of the spans, not their sum, is the time spent.

A sink is any object with ``tracer`` and ``registry`` attributes, read
on every event (an engine, whose tracer may be bound late).  Whether a
tracer is bound or not, each event bumps the sink registry's
``jit.events`` and ``jit.seconds`` counters: a recompile alarm.  Sinks
are held weakly; ``detach`` drops one at once.
"""
from __future__ import annotations

import threading
import time
import weakref
from typing import Any, List

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}

_lock = threading.Lock()
_sinks: "weakref.WeakSet[Any]" = weakref.WeakSet()
_listening = False


def _on_event(event: str, start_time: float, end_time: float,
              **kwargs) -> None:
    name = EVENTS.get(event)
    if name is None:
        return
    end = time.perf_counter()
    dur = max(end_time - start_time, 0.0)
    with _lock:
        sinks = list(_sinks)
    for sink in sinks:
        reg = sink.registry
        if reg.enabled:
            reg.counter("jit.events").inc()
            reg.counter("jit.seconds").inc(dur)
        tr = sink.tracer
        if tr.enabled:
            tr.complete(name, end - dur, end, fun=kwargs.get("fun_name"))


def attach(sink: Any) -> None:
    """Send JAX trace and compile events to ``sink`` from now on; the
    first call registers the process's one listener."""
    global _listening
    with _lock:
        if not _listening:
            import jax
            jax.monitoring.register_event_time_span_listener(_on_event)
            _listening = True
        _sinks.add(sink)


def detach(sink: Any) -> None:
    with _lock:
        _sinks.discard(sink)


def sinks() -> List[Any]:
    """The attached sinks that are still alive."""
    with _lock:
        return list(_sinks)
