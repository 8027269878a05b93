"""Arrival schedules, due-time pacing and seeding.

An open-loop schedule offers ``round(rate * seconds)`` arrivals.  Every seed
gets the same multiset of inter-arrival gaps (the exponential quantiles of
the rate, scaled to fill the window exactly) in its own order, so seeds
change the order of the work and never its amount.  Latency is taken from
each request's due time, so a generator that falls behind charges its
lateness to the requests, and the pacer reports how late it ran.
"""

from __future__ import annotations

import math
import random
import time

import jax


def seed_key(seed: int):
    """A JAX PRNG key from any whole-number seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def gaps(rate: float, seconds: float, seed: int) -> list[float]:
    """The schedule's inter-arrival gaps, in the seed's order; they sum to
    ``seconds``."""
    n = max(1, round(rate * seconds))
    g = [-math.log1p(-(i + 0.5) / n) for i in range(n)]
    scale = seconds / sum(g)
    g = [x * scale for x in g]
    random.Random(seed).shuffle(g)
    return g


def arrivals(rate: float, seconds: float, seed: int) -> list[float]:
    """Due times (s from the window's start): the first at 0, each next one
    a gap later."""
    out, t = [], 0.0
    for g in gaps(rate, seconds, seed):
        out.append(t)
        t += g
    return out


class Pacer:
    """Sleeps to each due time on the monotonic clock and records how late
    each hand-off ran."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.late_s: list[float] = []

    def wait_until(self, due: float) -> float:
        """Sleep to ``t0 + due``; return the absolute due time."""
        t_due = self.t0 + due
        delay = t_due - time.monotonic()
        if delay > 0:
            with jax.profiler.TraceAnnotation("bench/sleep"):
                time.sleep(delay)
        self.late_s.append(max(0.0, time.monotonic() - t_due))
        return t_due
