"""Where the program runs: Pallas interpret mode and the compile cache.

Pallas kernels are compiled by Mosaic when their operands live on a TPU and
run in interpret mode everywhere else (the CPU test host).  That decision
is made here and nowhere else; no caller passes it down as an option.

The persistent compile cache is switched on only by entry points (the
serving launcher, ``chip_smoke.py``), never at library import.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# repository root: src/repro/platform.py -> <repo>
REPO_ROOT = Path(__file__).resolve().parents[2]


def pallas_interpret(*arrays) -> bool:
    """True exactly when the arrays are not on a TPU.

    The first concrete array decides.  Tracers carry no device, so a call
    made while tracing (or with no array at all) asks the default backend,
    which is where the traced computation will run."""
    for x in arrays:
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
            return any(d.platform != "tpu" for d in x.devices())
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself, so
    no other directory is configured); otherwise the cache lives at the
    fixed path ``<repo>/.jax_cache``.  The path is part of every entry's
    key, so it never depends on a temp name, a pid or the time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels and per-equation programs compile in well under the default
    # one-second floor; keep them too, a cold chip call recompiles them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
