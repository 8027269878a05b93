"""Measured two-engine overlap from a trace's engine-track spans.

:func:`overlap_from_trace` reduces the engine-track spans (the stream
events' realized busy intervals) to the same both-busy/any-busy ratio
:class:`~repro.serving.stats.ServerStats` measures — the two must agree,
they are the same intervals through two pipelines.
"""

from __future__ import annotations

from repro.runtime.streams import intersect_seconds, merge_intervals

__all__ = ["overlap_from_trace"]


def overlap_from_trace(tracer, engines: tuple[str, ...] = ("tmu", "tpu"),
                       ) -> dict:
    """Reduce engine-track spans to measured two-engine overlap."""
    lanes = []
    busy = {}
    for engine in engines:
        merged = merge_intervals([(s.t_start, s.t_end)
                                  for s in tracer.spans(track=engine)])
        lanes.append(merged)
        busy[engine] = sum(t1 - t0 for t0, t1 in merged)
    both = intersect_seconds(lanes[0], lanes[1]) if len(lanes) == 2 else 0.0
    any_busy = sum(busy.values()) - both
    return {
        "engine_busy_s": busy,
        "any_busy_s": any_busy,
        "both_busy_s": both,
        "overlap_ratio": both / any_busy if any_busy > 0 else 0.0,
    }
