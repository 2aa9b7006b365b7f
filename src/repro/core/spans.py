"""Named host spans for the JAX profiler.

``span(name, **ids)`` is a ``jax.profiler.TraceAnnotation``.  While a
``jax.profiler.trace`` (or ``start_trace``) is capturing, each span is one
event in the trace's host plane, on its thread's own line and on the same
clock as the device planes, carrying ``ids`` as its stats.  With the
profiler off, entering and leaving one costs about a microsecond.

The FFCz service and engine name their stages ``ffcz.<stage>`` (one dot;
``ffcz.polish.round`` marks one float64 round trip inside a polish).  The
list, with the thread each runs on, is in docs/serving.md.
"""

from __future__ import annotations

import jax


def span(name: str, **ids):
    """A context manager that records ``name`` with ``ids`` while profiling."""
    return jax.profiler.TraceAnnotation(name, **ids)
