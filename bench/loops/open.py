"""Open loop: requests (or sessions) arrive on a seeded schedule at a fixed
rate whether or not the deployment keeps up, so a backlog lands on the
server.  Each arrival is handed to a worker thread at its due time and timed
from that due time.  Every request due in the window runs to completion."""

from __future__ import annotations

import concurrent.futures
import time

import jax

from bench.loops import LoopResult, Record, serve_one
from bench.schedule import Pacer, arrivals

# how long requests due in the window may take to finish after it closes
DRAIN_S = 120.0


def run(dep, traffic: dict, seed: int, seconds: float) -> LoopResult:
    due = arrivals(float(traffic["rate"]), seconds, seed)
    records = []
    futures = []
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=int(traffic["workers"]),
            thread_name_prefix="bench-client") as pool:
        t0 = time.monotonic() + 0.05
        pacer = Pacer(t0)
        with jax.profiler.TraceAnnotation("bench/window"):
            for i, t in enumerate(due):
                rec = Record(index=i, t_due=pacer.wait_until(t))
                records.append(rec)
                futures.append(pool.submit(serve_one, dep, rec))
            t_close = t0 + seconds
            left = t_close - time.monotonic()
            if left > 0:
                with jax.profiler.TraceAnnotation("bench/sleep"):
                    time.sleep(left)
        _, pending = concurrent.futures.wait(futures, timeout=DRAIN_S)
        for f in futures:
            if f.done():
                f.result()
        unfinished = len(pending)
        for f in pending:
            f.cancel()
    return LoopResult(records=records, t0=t0, t_close=t_close,
                      late_s=pacer.late_s, unfinished=unfinished)
