"""The profiler sink: the program's spans on the JAX profiler's clock.

``ProfilerTracer`` is a tracer like the others (install it with
``set_tracer(ProfilerTracer())`` or pass it as ``trace=``), but its
synchronous spans are ``jax.profiler.TraceAnnotation``s named
``repro.<span name>`` instead of ring-buffer records. While the profiler
records (``jax.profiler.start_trace``), each span lands in the
``.xplane.pb`` on the calling thread's host line, nested under any
annotation the caller opened around it, on the clock of the device's
operations; with no profiler session an annotation costs its
construction only.

The ring is off: ``spans()`` and ``counters()`` stay empty, span
attributes are dropped (the annotation's name is fixed), and async spans
(``begin``), instants (``event``) and counters (``count``) are the
disabled tracer's no-ops. A Chrome export of this tracer is empty; use a
``Tracer`` for that."""
from __future__ import annotations

from jax.profiler import TraceAnnotation

from .tracer import NullTracer

__all__ = ["ProfilerTracer", "SPAN_PREFIX"]

#: prefix of every program span's annotation name in a profiler trace
SPAN_PREFIX = "repro."


class _ProfiledSpan(TraceAnnotation):
    """One sync span as a profiler annotation; ``set`` is accepted and
    dropped so call sites stay tracer-agnostic."""

    def set(self, **attrs):
        return self


class ProfilerTracer(NullTracer):
    """Sync spans become profiler annotations; everything else is the
    disabled tracer's no-op."""

    enabled = True

    def span(self, name: str, **attrs) -> _ProfiledSpan:
        return _ProfiledSpan(SPAN_PREFIX + name)
