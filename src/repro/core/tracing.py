"""Host spans of the runtime on the profiler's own clock.

Every span is a ``jax.profiler.TraceAnnotation`` named ``hetgpu.<step>``,
with its counters as keyword arguments (they come back from the trace
as the event's stats).  A span is inert unless a profiler session is
running, so tracing is off whenever nobody traces: there is no switch.
Spans nest on the calling thread, so a reader can give each stretch of
host time to the innermost step that held it.

``span`` is the plain form.  ``timed`` also keeps the span's own
duration on the host clock (``.ms``), for a step the runtime reports in
its stats as well (``HetSession.stats["last_migration"]``): one pair of
clock readings serves both.
"""
from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

PREFIX = "hetgpu."


def span(name: str, **args) -> TraceAnnotation:
    """A span ``hetgpu.<name>`` carrying ``args`` as its counters."""
    return TraceAnnotation(PREFIX + name, **args)


class timed(TraceAnnotation):
    """A span ``hetgpu.<name>`` that also records its duration in ``ms``."""

    def __init__(self, name: str, **args):
        super().__init__(PREFIX + name, **args)
        self.ms = 0.0

    def __enter__(self) -> "timed":
        super().__enter__()
        self._t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        self.ms = (perf_counter() - self._t0) * 1e3
        return super().__exit__(*exc)
