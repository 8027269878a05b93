"""Set-up: compile and run every shape class a cell's traffic uses.

Every (class, height) is pre-compiled through ``TMServer.prewarm`` (on the
server's admission workers, two at a time), then executed once through
``TMServer.submit`` as a group of exactly that height, so that each
program's first run, which builds its jitted phases and kernels, falls in
set-up and not in the measured window.
"""

from __future__ import annotations

import time

import jax

WAIT_S = 1500.0


def _entry(server, fn_key, args, height):
    """The cache entry of ``args`` stacked to ``height``, or None."""
    shapes = tuple((height,) + tuple(a.shape)
                   for a in jax.tree_util.tree_leaves(args))
    for e in server.cache.entries():
        if e.key.fn_key == fn_key and e.key.shapes == shapes:
            return e
    return None


def warm_classes(server, classes, heights, drive, tries: int = 3) -> None:
    """Compile every (class, height) through ``TMServer.prewarm``, then run
    each height once as real traffic: ``drive(h)`` submits ``h`` requests
    at once at every stage, so each stage forms one group of height ``h``
    whose inputs are what the window's will be (host tokens or images,
    caches and grids from earlier responses).  Retried until every entry
    has served a group; raises when a class cannot be warmed."""
    for _, fn, args, key in classes:
        for h in heights:
            server.prewarm(fn, *args, fn_key=key, height=h)
    deadline = time.monotonic() + WAIT_S
    todo = [(key, args, h) for _, _, args, key in classes for h in heights]
    while any(_entry(server, k, a, h) is None for k, a, h in todo):
        if time.monotonic() > deadline:
            raise TimeoutError("prewarm did not finish")
        time.sleep(0.05)
    for h in heights:
        for _ in range(tries):
            drive(h)
            cold = [label for label, _, args, key in classes
                    if not _entry(server, key, args, h).demand_hits]
            if not cold:
                break
        else:
            raise RuntimeError(f"no group of height {h} formed for {cold}")

