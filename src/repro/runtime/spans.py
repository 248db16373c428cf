"""Profiler spans of the program's own layers.

Every span is a `jax.profiler.TraceAnnotation` named ``repro.<layer>``: it
lands in the profiler's trace on the same clock as the device's operations
when a profile is being taken (`jax.profiler.start_trace`, or a profiler
server), and costs about a microsecond when none is.  There is no other
switch.  Metadata is kept to cheap ints (``seq``, ``request``,
``requests``, ``chains``, ``epoch``), written into the trace as the
event's stats.

`named_jit` gives a jitted function a stable name, so its XLA module shows
in a device trace as ``jit_<name>``, and marks each trace of it with a
``repro.retrace.<name>`` span.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro."


def span(name: str, **meta: int) -> jax.profiler.TraceAnnotation:
    """A profiler span ``repro.<name>``; use it as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **meta)


def named_jit(fn, name: str, **jit_kw):
    """`jax.jit` of ``fn`` under the name ``name``.

    The compiled module is ``jit_<name>``.  The body runs only while JAX
    traces it, so the ``repro.retrace.<name>`` span it opens counts the
    traces, at no cost to a call that hits the compiled executable.
    """
    retrace = "retrace." + name

    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with span(retrace):
            return fn(*args, **kwargs)

    traced.__name__ = traced.__qualname__ = name
    return jax.jit(traced, **jit_kw)
