"""The served path's audit, copied from ``chip_smoke.py``: lowering paths,
engine declines, launches, donation, compile cache and fault counters, and
every degraded lowering, quarantine, ladder fallback, group fault or
declined jit as a problem.  The benchmark prints it, and a run with any
problem is not correct."""

from __future__ import annotations

import collections

import jax


def audit(server) -> dict:
    """Lowering paths, declines, launches, cache and fault counters of a
    server's entries, with every degradation listed under ``problems``."""
    from repro.compiler.api import _JIT_DECLINED, TPUPhaseReport
    problems = []
    paths: collections.Counter = collections.Counter()
    declines: collections.Counter = collections.Counter()
    launches = collections.Counter()
    donated = 0
    entries = server.cache.entries()
    for e in entries:
        name = str(e.key.fn_key)
        if e.quarantine:
            problems.append(f"{name}: quarantined {sorted(e.quarantine)}")
        if e.degraded_phases:
            problems.append(f"{name}: phases fell down the backend ladder "
                            f"{e.degraded_phases}")
        # why a chain or crossing the admission sweep modeled did not realize
        for sweep in ("fuse_chains", "cross_engine"):
            for why in e.selection.get(sweep, {}).get("declines", ()):
                declines[f"admission {sweep} probe: {why}"] += 1
        for ph in e.compiled.partition_report.phases:
            if ph.kind == "tpu" and ph.jit_fn is _JIT_DECLINED:
                problems.append(f"{name}: TPU phase {ph.index} jit declined")
            donated += len(getattr(ph, "donated", None) or ())
        for rep in e.lowerings.values():
            if isinstance(rep, TPUPhaseReport):
                paths["xla"] += rep.n_eqns
                launches["xla computations"] += rep.xla_computations
                continue
            for why in rep.declines:
                declines[why] += 1
            for r in rep.records:
                if r.degraded:
                    problems.append(f"{name}: degraded {r.path} ({r.reason})")
                kind = r.path.split(".")[0]
                paths[r.path if kind == "pallas" else
                      ("engine" if kind == "reference" else "xla")] \
                    += r.instrs
                launches["pallas" if kind == "pallas" else
                         ("engine" if kind == "reference" else
                          "xla computations")] += r.launches
                if r.reason:
                    declines[r.reason] += 1
    if jax.default_backend() == "tpu" and not donated:
        problems.append("no TPU phase donated a buffer")
    snap = server.snapshot_stats()
    for k in ("degraded_phases", "group_faults", "isolation_retries"):
        if snap[k]:
            problems.append(f"server {k} = {snap[k]}")
    return {"problems": problems, "entries": len(entries), "paths": dict(paths),
            "declines": dict(declines), "launches": dict(launches),
            "donated_buffers": donated,
            "cache_hits": snap["cache"]["hits"],
            "cache_misses": snap["cache"]["misses"],
            "compile_s": sum(e.compile_s for e in entries)}
