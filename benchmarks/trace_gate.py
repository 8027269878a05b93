"""Tracing gate — observability must be cheap, complete, and honest.

One serving workload (``espcn``: conv compute feeding a TM tail, so both
engines run) is measured twice through :class:`TMServer` — untraced and
traced — and the traced run's timeline is checked against three gates:

* **completeness** — every phase of the compiled program has >= 1
  ``phase/{index}/...`` span in the trace (nothing executes unobserved);
* **overhead** — traced warm throughput within ``MAX_OVERHEAD`` (5%) of
  untraced; both servers stay warm, each of the ``N_PASSES`` measured
  rounds runs one pass per mode, and the within-round order ALTERNATES
  each round (going first measurably flatters a pass).  The gated
  statistic is BEST wall vs BEST wall: per-pass walls swing tens of
  percent under machine load, so the minimum — each mode's least-noise
  observation of its cost floor — is the only estimator tight enough for
  a 5% gate (the per-round ratio median is reported as a diagnostic);
* **agreement** — the per-engine-track both-busy overlap recomputed from
  the exported spans (:func:`repro.obs.overlap_from_trace`) matches
  ``ServerStats.overlap_ratio()`` within ``MAX_OVERLAP_DELTA`` (0.02) —
  the trace and the stats must describe the same execution.

Artifacts: ``BENCH_trace.json`` (gate numbers) and ``serving.trace.json``
(the Chrome-trace timeline; open at https://ui.perfetto.dev).

    PYTHONPATH=src python benchmarks/trace_gate.py
"""

from __future__ import annotations

import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.models import cnn
from repro.obs import Tracer, overlap_from_trace
from repro.serving import ServerConfig, TMServer

SHAPE = (1, 40, 48, 3)          # request image: large enough that per-phase
                                # work dwarfs the fixed per-record trace cost
N_REQUESTS = 16                 # per warm pass
N_PASSES = 20                   # paired rounds (even: the alternating
                                # order stays balanced); passes are ~0.1s,
                                # so many rounds cost little and tighten
                                # the per-mode best-wall estimate
MAX_OVERHEAD = 0.05             # traced warm throughput within 5% of untraced
MAX_OVERLAP_DELTA = 0.02        # trace-derived vs stats overlap agreement
TRACE_PATH = "serving.trace.json"


def main() -> dict:
    params = cnn.init_espcn(jax.random.PRNGKey(0), s=2)

    def espcn(img):
        return cnn.espcn(params, img)

    rng = np.random.RandomState(0)
    # one request stream, shared by every pass of BOTH servers — the modes
    # must differ only in tracing, never in data
    imgs = [jnp.asarray(rng.rand(*SHAPE).astype(np.float32))
            for _ in range(N_REQUESTS)]

    def one_pass(srv):
        t0 = time.perf_counter()
        futs = [srv.submit(espcn, img, fn_key="espcn") for img in imgs]
        for f in futs:
            f.result(timeout=300)
        return time.perf_counter() - t0

    tracer = Tracer()
    results = {}
    with TMServer(ServerConfig(max_batch=2,
                               batch_timeout_s=0.005)) as srv_un, \
         TMServer(ServerConfig(max_batch=2, batch_timeout_s=0.005,
                               trace=tracer)) as srv_tr:
        one_pass(srv_un)                        # cold: compiles here
        one_pass(srv_tr)
        walls_un, walls_tr = [], []
        for i in range(N_PASSES):               # interleave measured passes,
            order = [(srv_un, walls_un), (srv_tr, walls_tr)]
            if i % 2:                           # alternating who goes first
                order.reverse()
            for srv, walls in order:
                walls.append(one_pass(srv))
        for key, srv, walls in (("untraced", srv_un, walls_un),
                                ("traced", srv_tr, walls_tr)):
            best = min(walls)
            results[key] = {
                "warm_walls_s": walls,
                "best_wall_s": best,
                "best_requests_per_s": N_REQUESTS / best,
                "stats": srv.snapshot_stats(),
            }
        compiled = srv_tr.cache.get(srv_tr.cache.keys()[0]).compiled
    untraced, traced = results["untraced"], results["traced"]

    # --- completeness: >= 1 span per phase of the compiled program --------
    n_phases = len(compiled.partition_report.phases)
    spans_per_phase = {
        p.index: len(tracer.spans(prefix=f"phase/{p.index}/"))
        for p in compiled.partition_report.phases}
    unobserved = sorted(i for i, n in spans_per_phase.items() if n == 0)

    # --- overhead: best traced wall vs best untraced wall -----------------
    overhead = traced["best_wall_s"] / untraced["best_wall_s"] - 1.0
    ratios = sorted(t / u for t, u in zip(traced["warm_walls_s"],
                                          untraced["warm_walls_s"]))
    mid = len(ratios) // 2
    median_ratio = (ratios[mid] if len(ratios) % 2
                    else 0.5 * (ratios[mid - 1] + ratios[mid]))

    # --- agreement: overlap from the trace vs from ServerStats ------------
    stats_overlap = traced["stats"]["overlap_ratio"]
    trace_overlap = overlap_from_trace(tracer)
    overlap_delta = abs(trace_overlap["overlap_ratio"] - stats_overlap)

    # --- integrity + artifacts --------------------------------------------
    nesting = tracer.nesting_errors()
    trace = tracer.export_chrome_trace(TRACE_PATH)

    report = {
        "benchmark": "trace_gate",
        "untraced": {k: v for k, v in untraced.items() if k != "stats"},
        "traced": {k: v for k, v in traced.items() if k != "stats"},
        "round_ratios": ratios,
        "median_round_ratio": median_ratio,
        "overhead": overhead,
        "max_overhead": MAX_OVERHEAD,
        "phases": n_phases,
        "spans_per_phase": spans_per_phase,
        "unobserved_phases": unobserved,
        "overlap_stats": stats_overlap,
        "overlap_trace": trace_overlap["overlap_ratio"],
        "overlap_delta": overlap_delta,
        "max_overlap_delta": MAX_OVERLAP_DELTA,
        "nesting_errors": nesting,
        "trace_events": len(trace["traceEvents"]),
    }

    print("# trace_gate (espcn through TMServer, traced vs untraced)")
    print(f"untraced warm: {untraced['best_requests_per_s']:.1f} req/s | "
          f"traced warm: {traced['best_requests_per_s']:.1f} req/s "
          f"(best-wall overhead {overhead:+.1%}, gate {MAX_OVERHEAD:.0%}; "
          f"median round ratio {median_ratio:.3f})")
    print(f"phase spans: {spans_per_phase} over {n_phases} phases")
    print(f"overlap: {stats_overlap:.3f} stats vs "
          f"{trace_overlap['overlap_ratio']:.3f} trace "
          f"(delta {overlap_delta:.4f}, gate {MAX_OVERLAP_DELTA})")
    print(f"trace: {len(trace['traceEvents'])} events -> {TRACE_PATH}")

    with open("BENCH_trace.json", "w") as f:
        json.dump(report, f, indent=2)
    print("\nwrote BENCH_trace.json")

    if unobserved:
        raise SystemExit(f"phases executed without a span: {unobserved}")
    if nesting:
        raise SystemExit(f"trace integrity violated: {nesting}")
    if overhead > MAX_OVERHEAD:
        raise SystemExit(
            f"tracing overhead {overhead:.1%} exceeds the "
            f"{MAX_OVERHEAD:.0%} gate "
            f"({traced['best_requests_per_s']:.1f} traced vs "
            f"{untraced['best_requests_per_s']:.1f} untraced req/s)")
    if overlap_delta > MAX_OVERLAP_DELTA:
        raise SystemExit(
            f"trace-derived overlap {trace_overlap['overlap_ratio']:.3f} "
            f"disagrees with ServerStats {stats_overlap:.3f} "
            f"(delta {overlap_delta:.4f} > {MAX_OVERLAP_DELTA})")
    return report


if __name__ == "__main__":
    main()
