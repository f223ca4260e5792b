"""Tracing & metrics for the decode pipeline, with no dependency beyond
JAX (which only the profiler sink imports).

Where a window's latency goes — queue wait vs batch pack vs kernel
launch vs retire — and what the planner/plan-cache actually decided, as
(1) nestable spans with structured attributes (``tracer``), (2) fixed-
bucket latency/size histograms (``hist``), and (3) exportable artifacts:
Chrome trace-event JSON for Perfetto and a Prometheus text exposition
(``export``).

Enable for a whole process with one call (everything that resolved
``trace=None`` through :func:`get_tracer` lights up)::

    from repro.obs import Tracer, set_tracer, write_chrome_trace
    tracer = Tracer()
    set_tracer(tracer)
    ... run the server / stream ...
    write_chrome_trace(tracer, "trace.json")   # open in Perfetto

or pass ``trace=tracer`` to ``DecodeServer`` / ``StreamDecoder``
explicitly. Disabled (the default) the whole layer is a shared no-op
object — nothing allocates on the hot path.

The profiler sink (``profiler.ProfilerTracer``, installed the same way)
puts every synchronous span on the JAX profiler's clock instead: each
becomes a ``jax.profiler.TraceAnnotation`` named ``repro.<span name>`` in
the ``.xplane.pb``, beside the device's operations. Its ring is off (no
records, no counters, attributes dropped); async spans and instants do
not reach the profiler.

The serve path's synchronous spans (``DecodeServer``):

  * ``push``, with ``push_sanitize`` (validation and sanitizing),
    ``push_admit`` (the backpressure projection), ``push_stage`` (raw
    concatenation, depuncture, buffer concatenation) and ``push_frame``
    (window extraction, host framing, enqueue) inside it, opened only
    when the tracer records (``enabled``), so the disabled path enters
    no sub-span at all;
  * ``launch``, with ``batch_pack``, ``h2d`` (the batch's host-to-device
    copy), ``launch_attempt`` (or ``degrade``) inside it;
  * ``retire``, with ``retire_wait`` (the device sync and device-to-host
    copy of one launch's bits) inside it;
  * ``evacuate``, ``readmit``, ``breaker_probe``; ``plan_build`` in the
    plan cache.
"""
from .tracer import (Tracer, NullTracer, NULL_TRACER,      # noqa: F401
                     SpanRecord, get_tracer, set_tracer)
from .hist import (Histogram, geometric_bounds,            # noqa: F401
                   LATENCY_MS_BOUNDS, SIZE_BOUNDS)
from .export import (chrome_trace, write_chrome_trace,     # noqa: F401
                     prometheus_text, write_metrics_json)
from .profiler import ProfilerTracer                     # noqa: F401

__all__ = ["Tracer", "NullTracer", "NULL_TRACER", "SpanRecord",
           "ProfilerTracer", "get_tracer", "set_tracer", "Histogram",
           "geometric_bounds", "LATENCY_MS_BOUNDS", "SIZE_BOUNDS",
           "chrome_trace", "write_chrome_trace", "prometheus_text",
           "write_metrics_json"]
