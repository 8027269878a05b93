"""Load loops: how a traffic mix offers its requests to a deployment.

A loop module exposes ``run(dep, traffic, seed, seconds) -> LoopResult``.
The traffic file names its loop (``"loop": "open"``).  Each
request is served by ``dep.serve(i, rec)``, which stamps ``rec.events``
with the monotonic time of each token or answer as it reaches the host.
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Record:
    """One request: due time, answer times, outcome."""

    index: int
    t_due: float
    events: list = dataclasses.field(default_factory=list)
    t_done: float | None = None
    error: str | None = None
    kept: object = None            # served output kept for the check

    @property
    def ok(self) -> bool:
        return self.t_done is not None and self.error is None


@dataclasses.dataclass
class LoopResult:
    records: list                  # every request issued (due in the window)
    t0: float                      # window start (monotonic)
    t_close: float                 # window end
    late_s: list                   # generator lateness per hand-off
    unfinished: int                # requests that never completed


def serve_one(dep, rec: Record) -> None:
    """Serve ``rec`` through ``dep``; a raising request is recorded as
    failed, never lost."""
    try:
        rec.kept = dep.serve(rec.index, rec)
        rec.t_done = time.monotonic()
    except Exception as e:  # noqa: BLE001 — a failed request is a result
        rec.error = f"{type(e).__name__}: {e}"
