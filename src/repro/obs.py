"""Spans and a compile count that the JAX profiler records.

``span(name)`` is a ``jax.profiler.TraceAnnotation``: while a profiler
trace runs, it lands in the trace's host plane on the same clock as the
device's ops, so a trace reduction can attribute device time and idle
gaps to what the program was doing.  With no profiler running, a span
costs only the annotation's construction.  Span names are dotted, with
no space or colon (``serve.step``), so reductions that drop the
runtime's own events keep them.

``watch_compiles()`` registers one ``jax.monitoring`` listener per
process.  JAX records ``/jax/core/compile/backend_compile_duration``
around every executable it builds, whether the backend compiled it or
it was loaded from the persistent compilation cache (the cache-hit event
fires inside that span and is not counted again).  Each one increments
``compiles()`` and emits a ``repro.compile`` span, so a trace shows
every compile that fell inside its window.  The count is process-wide,
as the listener table it hangs on is.
"""
from __future__ import annotations

import threading

import jax

COMPILE_SPAN = "repro.compile"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_watching = False
_compiles = 0


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` in the profiler's trace (no-op cost
    when no profiler runs)."""
    return jax.profiler.TraceAnnotation(name)


def compiles() -> int:
    """Executables built (compiled or loaded from the persistent cache)
    in this process since ``watch_compiles()`` was first called."""
    return _compiles


def watching() -> bool:
    """Whether this process counts compiles."""
    return _watching


def watch_compiles() -> None:
    """Start counting compiles in this process; later calls do nothing."""
    global _watching
    with _lock:
        if _watching:
            return
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _compiles
    if event != _BACKEND_COMPILE_EVENT:
        return
    with _lock:
        _compiles += 1
    with span(COMPILE_SPAN):
        pass
