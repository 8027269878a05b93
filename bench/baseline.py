"""``--baseline``: each served shape class of a cell timed through the
server and under plain ``jax.jit`` on the same chip (ROADMAP's yardstick:
a TM phase earns its place only where it beats XLA's own lowering)."""

from __future__ import annotations

import sys
import time

import jax
import numpy as np

from bench import harness


def time_classes(server, classes, heights, reps: int = 10) -> list[dict]:
    """Median wall time per call of each class at each height, through the
    server (one group of that height) and under plain ``jax.jit`` of the
    same batched function on the same chip."""
    rows = []
    for label, fn, args, key in classes:
        for h in heights:
            stacked = jax.tree_util.tree_map(
                lambda *xs: jax.numpy.stack(xs), *([args] * h))
            jitted = jax.jit(jax.vmap(fn))
            jax.block_until_ready(jitted(*stacked))
            served, plain = [], []
            for _ in range(reps):
                t = time.perf_counter()
                futs = [server.submit(fn, *args, fn_key=key)
                        for _ in range(h)]
                jax.block_until_ready([f.result() for f in futs])
                served.append(time.perf_counter() - t)
                t = time.perf_counter()
                jax.block_until_ready(jitted(*stacked))
                plain.append(time.perf_counter() - t)
            rows.append({"class": label, "height": h,
                         "served_ms": float(np.median(served)) * 1e3,
                         "jit_ms": float(np.median(plain)) * 1e3})
    return rows


def run(workload: str, seed: int, *, t_process: float) -> dict:
    cell = harness.Cell.load(workload)
    device = harness.device_line(cell.chips)
    from repro.platform import enable_compile_cache
    enable_compile_cache()
    dep = cell.deployment_class()(cell.spec, cell.traffic, seed)
    try:
        dep.warm()
        setup_s = time.monotonic() - t_process
        rows = time_classes(dep.server, dep.classes(), dep.heights)
    finally:
        dep.stop()
    for r in rows:
        print(f"baseline {r['class']} x{r['height']}: served "
              f"{r['served_ms']:.3f} ms, jax.jit {r['jit_ms']:.3f} ms",
              file=sys.stderr)
    return {"baseline": rows, "setup_s": setup_s, "device": device}
