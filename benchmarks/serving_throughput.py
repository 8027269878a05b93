"""Serving-runtime benchmark — the perf trajectory of ``repro.serving``.

Three measurements, emitted as ``BENCH_serving.json`` (archived per commit
by CI, like the compiler trajectory):

* **uncached per-request baseline** — every request pays a fresh
  ``tm_compile`` + execution, the pre-serving workflow;
* **throughput vs. batch size** — a warm :class:`TMServer` at
  ``max_batch`` in {1, 2, 4, 8}: cache-cold admission latency (first pass)
  vs. cache-warm batched throughput (second pass);
* **pipeline overlap** — mixed conv+TM traffic (``espcn``) through the
  two-engine pipeline: measured overlap ratio next to the cycle model's
  prediction.  This pass runs traced (``--trace out.json`` exports its
  Chrome-trace timeline).

Acceptance gate: warm batched serving must clear 2x the uncached
per-request throughput (the compile cache + micro-batching dividend).

    PYTHONPATH=src python benchmarks/serving_throughput.py [--trace out.json]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.compiler import tm_compile
from repro.models import cnn
from repro.obs import Tracer
from repro.serving import ServerConfig, TMServer

SHAPE = (1, 8, 12, 8)          # superres_tail request: x (B,H,W,C), s=2
N_REQUESTS = 16                 # per measured server pass
N_UNCACHED = 8                  # uncached baseline sample size


def _request(rng):
    b, h, w, c = SHAPE
    x = jnp.asarray(rng.rand(b, h, w, c).astype(np.float32))
    skip = jnp.asarray(rng.rand(b, h * 2, w * 2, c // 4).astype(np.float32))
    return x, skip


def bench_uncached(rng) -> dict:
    """Every request: fresh tm_compile + one execution (no cache, batch=1).

    One discarded warmup request first, so one-time jax/XLA jit warmup (which
    the serving path amortizes identically) does not pad the baseline — the
    measured cost is the genuinely per-request work: retrace + passes +
    partition + execution."""
    args = _request(rng)
    jax.block_until_ready(tm_compile(cnn.superres_tail, *args)(*args))
    walls = []
    for _ in range(N_UNCACHED):
        args = _request(rng)
        t0 = time.perf_counter()
        compiled = tm_compile(cnn.superres_tail, *args)
        jax.block_until_ready(compiled(*args))
        walls.append(time.perf_counter() - t0)
    total = sum(walls)
    return {
        "requests": N_UNCACHED,
        "wall_s": total,
        "latency_p50_s": sorted(walls)[len(walls) // 2],
        "requests_per_s": N_UNCACHED / total,
    }


def bench_server(rng, max_batch: int) -> dict:
    """One server: cold pass (admission) then warm measured pass."""
    cfg = ServerConfig(max_batch=max_batch, batch_timeout_s=0.005)
    with TMServer(cfg) as srv:
        def one_pass(n):
            reqs = [_request(rng) for _ in range(n)]
            t0 = time.perf_counter()
            futs = [srv.submit(cnn.superres_tail, *a, fn_key="superres")
                    for a in reqs]
            outs = [f.result(timeout=300) for f in futs]
            wall = time.perf_counter() - t0
            for args, out in zip(reqs, outs):
                assert np.array_equal(np.asarray(out),
                                      np.asarray(cnn.superres_tail(*args)))
            return wall

        cold_wall = one_pass(N_REQUESTS)      # admission compiles here
        warm_wall = one_pass(N_REQUESTS)      # all shape classes cached
        snap = srv.snapshot_stats()
    return {
        "max_batch": max_batch,
        "cold_wall_s": cold_wall,
        "warm_wall_s": warm_wall,
        "warm_requests_per_s": N_REQUESTS / warm_wall,
        "cold_latency_p50_s": snap["cold_latency_p50_s"],
        "warm_latency_p50_s": snap["warm_latency_p50_s"],
        "mean_batch_size": snap["mean_batch_size"],
        "pad_rows": snap["pad_rows"],
        "cache": snap["cache"],
        "exact": True,  # the pass asserts bit-exactness per request
    }


def bench_overlap(rng, tracer: Tracer) -> dict:
    """Mixed conv+TM traffic: the two-engine pipeline's overlap ratio,
    traced."""
    params = cnn.init_espcn(jax.random.PRNGKey(0), s=2)

    def espcn(img):
        return cnn.espcn(params, img)

    cfg = ServerConfig(max_batch=2, batch_timeout_s=0.005, trace=tracer)
    with TMServer(cfg) as srv:
        for _ in range(2):  # warm the cache, then measure steady traffic
            futs = [srv.submit(espcn,
                               jnp.asarray(rng.rand(1, 10, 14, 3)
                                           .astype(np.float32)),
                               fn_key="espcn")
                    for _ in range(8)]
            for f in futs:
                f.result(timeout=300)
        snap = srv.snapshot_stats()
    return {
        "overlap_ratio": snap["overlap_ratio"],
        "predicted_overlap": snap["predicted_overlap"],
        "engine_busy_s": snap["engine_busy_s"],
        "pipeline_span_s": snap["pipeline_span_s"],
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="export the traced overlap pass as Chrome-trace "
                         "JSON (open at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)

    rng = np.random.RandomState(0)
    uncached = bench_uncached(rng)
    rows = [bench_server(rng, mb) for mb in (1, 2, 4, 8)]
    tracer = Tracer()
    overlap = bench_overlap(rng, tracer)

    best = max(rows, key=lambda r: r["warm_requests_per_s"])
    speedup = best["warm_requests_per_s"] / uncached["requests_per_s"]
    report = {
        "benchmark": "serving_throughput",
        "uncached": uncached,
        "rows": rows,
        "overlap": overlap,
        "best_warm_requests_per_s": best["warm_requests_per_s"],
        "warm_over_uncached_speedup": speedup,
    }

    print("# serving_throughput (TMServer vs per-request tm_compile)")
    print(f"{'max_batch':>10s}{'warm req/s':>12s}{'cold p50':>12s}"
          f"{'warm p50':>12s}{'mean batch':>12s}{'hit rate':>10s}")
    for r in rows:
        print(f"{r['max_batch']:>10d}{r['warm_requests_per_s']:>12.1f}"
              f"{r['cold_latency_p50_s'] * 1e3:>10.1f}ms"
              f"{r['warm_latency_p50_s'] * 1e3:>10.1f}ms"
              f"{r['mean_batch_size']:>12.2f}"
              f"{r['cache']['hit_rate']:>10.2f}")
    print(f"uncached baseline: {uncached['requests_per_s']:.2f} req/s "
          f"(p50 {uncached['latency_p50_s'] * 1e3:.0f} ms)")
    print(f"pipeline overlap: {overlap['overlap_ratio']:.1%} measured / "
          f"{overlap['predicted_overlap']:.1%} predicted (espcn)")
    print(f"warm-batched over uncached: {speedup:.1f}x")

    with open("BENCH_serving.json", "w") as f:
        json.dump(report, f, indent=2)
    print("\nwrote BENCH_serving.json")
    if args.trace:
        trace = tracer.export_chrome_trace(args.trace)
        print(f"trace: {len(trace['traceEvents'])} events -> {args.trace}")
    if speedup < 2.0:
        raise SystemExit(
            f"cache-warm batched serving only {speedup:.2f}x over uncached "
            f"per-request execution (acceptance needs >= 2x)")
    return report


if __name__ == "__main__":
    main()
