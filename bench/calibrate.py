"""Calibration of a cell on the chip, in one process (a benchmark run never
calls it):

    python bench/calibrate.py --workload <cell> --seed <n> \
        [--rates 0.5,1,2] [--sweep-seconds 20] [--check-seconds 20] \
        [--control-seeds 3]

* ``--rates``: the knee sweep.  The cell's open loop runs at each offered
  rate for ``--sweep-seconds``; for each it prints the requests, how many
  finished, the median latency (due → done) of the first and the last
  fifth of the requests, and how long the last one took to finish after
  the window closed.  Then the knee (:func:`knee`) and the rate a cell
  runs at, four fifths of it.
* ``--check-seconds``: one window at the cell's own rate, then the
  correctness numbers of the served answers and the control's reading on
  the same prompts and tokens.
* ``--control-seeds``: the control's reading on that many more seeds
  (weights and inputs from each seed, at ``--control-sequences`` of the
  reference's own greedy sequences; no program needed).

Each result is one JSON line on standard output.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def _emit(**kw) -> None:
    print(json.dumps(kw, default=float), flush=True)


def sweep_point(dep, cell, rate: float, seconds: float, seed: int) -> dict:
    traffic = dict(cell.traffic, rate=rate)
    res = cell.loop().run(dep, traffic, seed, seconds)
    lat = [r.t_done - r.t_due if r.ok else float("inf") for r in res.records]
    fifth = max(1, len(lat) // 5)
    done = [r.t_done for r in res.records if r.ok]
    return {"rate": rate, "requests": len(lat),
            "finished": sum(r.ok for r in res.records),
            "p50_first_s": statistics.median(lat[:fifth]),
            "p50_last_s": statistics.median(lat[-fifth:]),
            "drain_s": (max(done) - res.t_close) if done else None,
            "late_max_s": max(res.late_s, default=0.0)}


def grows(row: dict) -> bool:
    """The backlog grew at this sweep point: a request failed, the last
    fifth waited 1.5x the first, or the drain after the window closed took
    more than twice the first fifth's median latency and 3 s."""
    return (row["finished"] < row["requests"]
            or row["p50_last_s"] > 1.5 * row["p50_first_s"]
            or (row["drain_s"] or 0.0) > max(2 * row["p50_first_s"], 3.0))


def knee(rows: list) -> float:
    """The highest swept rate below which no point grew; half the lowest
    rate when even that one grew."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate"]):
        if grows(row):
            break
        best = row["rate"]
    return best if best is not None else min(r["rate"] for r in rows) / 2


def control_reading(dep, recs) -> float:
    """The control's reading on the served prompts and tokens: its logit
    error (phi4mini) or grid error (yolov3tiny)."""
    if hasattr(dep, "control_error"):
        return dep.control_error()
    kept = [r for r in recs if r.kept["logits"] is not None]
    return float(dep.reference_errors(kept, quantized=True).max())


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", default="")
    p.add_argument("--sweep-seconds", type=float, default=20.0)
    p.add_argument("--check-seconds", type=float, default=0.0)
    p.add_argument("--control-seeds", type=int, default=0)
    p.add_argument("--control-sequences", type=int, default=16)
    a = p.parse_args()
    from bench import harness
    cell = harness.Cell.load(a.workload)
    device = harness.device_line(cell.chips)
    from repro.platform import enable_compile_cache
    enable_compile_cache()
    Dep = cell.deployment_class()
    dep = Dep(cell.spec, cell.traffic, a.seed)
    try:
        dep.warm()
        _emit(phase="setup", setup_s=time.monotonic() - T_PROCESS,
              device=device)
        rows = []
        for i, r in enumerate(x for x in a.rates.split(",") if x):
            rows.append(sweep_point(dep, cell, float(r), a.sweep_seconds,
                                    a.seed + 1 + i))
            _emit(phase="sweep", **rows[-1])
        if rows:
            k = knee(rows)
            _emit(phase="knee", knee=k, rate=round(0.8 * k, 3))
        kept = []
        if a.check_seconds:
            res = cell.loop().run(dep, cell.traffic, a.seed, a.check_seconds)
            kept = [r for r in res.records if r.ok]
    finally:
        dep.stop()
    if kept:
        _emit(phase="check", seed=a.seed, checks=dep.check(kept, a.seed),
              control=control_reading(dep, kept))
    for k in range(a.control_seeds):
        seed = a.seed + 1000 + k
        other = Dep(cell.spec, cell.traffic, seed)
        other.stop()
        if hasattr(other, "control_error"):
            _emit(phase="control", seed=seed, control=other.control_error())
            continue
        recs = other.reference_greedy(a.control_sequences)
        _emit(phase="control", seed=seed, control=float(
            other.reference_errors(recs, quantized=True).max()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
