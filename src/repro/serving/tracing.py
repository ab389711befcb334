"""Host spans on the profiler's clock.

``span(name, **args)`` opens a ``jax.profiler.TraceAnnotation``. While a
profiler session runs (``jax.profiler.trace`` / ``start_trace``) the span
and its args (host ints or strings) are recorded into the same
``.xplane.pb`` as the device's programs, so host and device time share one
clock; with no session it records nothing and costs about a microsecond.
Args known only later, or that cost more than reading a field, are set on
the open span with ``sp.set_metadata(...)`` behind ``sp.is_enabled()``.
The span names and args are listed in ``docs/serving.md``
("Observability").
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **args) -> TraceAnnotation:
    """A host span ``name`` carrying ``args``; use as a context manager."""
    return TraceAnnotation(name, **args)
