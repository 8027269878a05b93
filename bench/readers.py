"""The arithmetic behind the metric files in ``bench/metrics``.

End-to-end readers take the host clock: every request due in the window
counts, a failed one as ``inf`` (it misses the tail).  Per-layer readers
take the traced run: the server's own spans and queue-delay series
(``repro.obs``, ``ServerStats``) and the device trace (``bench.devtrace``).
Each returns None when it finds nothing to read.
"""

from __future__ import annotations

import math

from bench.stats import percentile, union_length


def token_gap_ms(ctx):
    xs = []
    for r in ctx.records:
        if not r.ok:
            xs.append(math.inf)
            continue
        xs.extend((b - a) * 1e3 for a, b in zip(r.events, r.events[1:]))
    return percentile(xs, 95) if xs else None


def answer_ms(ctx):
    xs = [(r.events[-1] - r.t_due) * 1e3 if r.ok and r.events else math.inf
          for r in ctx.records]
    return percentile(xs, 95) if xs else None


def queue_wait_ms(ctx):
    d = ctx.queue_delays
    return sum(d) / len(d) * 1e3 if d else None


def mfu(ctx):
    """Model FLOPs of the tokens or answers that reached the host in the
    window, over peak FLOP/s times the time some group was in flight."""
    flops = sum(ctx.event_flops(r.index, k)
                for r, k, _ in ctx.window_events())
    busy = union_length(ctx.inflight, ctx.t0, ctx.t_close)
    if not flops or not busy:
        return None
    return 100.0 * flops / (ctx.peak_flops * busy)


def _per_unit_ms(ctx, key):
    n = sum(1 for _ in ctx.window_events())
    if ctx.dev is None or not n:
        return None
    return ctx.dev[key] * 1e3 / n


def tm_kernel_ms(ctx):
    """Device time of the TM (Mosaic) kernels per token or answer."""
    return _per_unit_ms(ctx, "tm_s")


def xla_ms(ctx):
    """Device time of every other device op per token or answer."""
    return _per_unit_ms(ctx, "xla_s")


def idle_share(ctx):
    if ctx.dev is None or not ctx.dev["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.dev["busy_s"] / ctx.dev["window_s"])
