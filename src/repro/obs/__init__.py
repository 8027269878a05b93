"""Unified observability: span tracing, counters, and Perfetto export.

See :mod:`repro.obs.tracer` for the recorder and the clock anchor that puts
its spans on a ``jax.profiler`` trace's clock, :mod:`repro.obs.hooks` for
the compile and garbage-collection spans, and :mod:`repro.obs.report` for
the engine-overlap reduction.  ``docs/observability.md`` documents the span
taxonomy and counter names.
"""

from repro.obs.hooks import HostHooks
from repro.obs.report import overlap_from_trace
from repro.obs.tracer import (CLOCK_ANCHOR, NULL_TRACER, NullTracer,
                              SpanRecord, Tracer, as_tracer, clock_anchor)

__all__ = [
    "CLOCK_ANCHOR", "HostHooks", "NULL_TRACER", "NullTracer", "SpanRecord",
    "Tracer", "as_tracer", "clock_anchor", "overlap_from_trace",
]
