"""Block-mode ``tm_affine`` launches compile once per static signature.

Each test counts backend compiles with a ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration``.  Kernels run in interpret
mode on the CPU.  The launch cache is process-wide, so every case uses a
shape no other test in this file uses: a "new" signature is new to the
process.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import tm_compile
from repro.core import affine as af
from repro.kernels.tm_affine.tm_affine import (analyze_block_mode,
                                               block_launch_cache_info,
                                               tm_affine)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def count_compiles():
    """Yield a one-item list holding the number of backend compiles so
    far inside the block."""
    n = [0]

    def on_duration(event: str, secs: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield n
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _call(x, m, mode, ew):
    y = None if ew is None else jnp.ones(m.out_shape, x.dtype)
    out = tm_affine(x, m, interpret=True, force_mode=mode, y=y, ew=ew)
    return np.asarray(out.block_until_ready())


# case: (shape, mode, ew, what differs in the second call)
CASES = {
    "block-same": ((8, 16, 128), None, None, "nothing"),
    "block-ew-same": ((8, 24, 128), None, "add", "nothing"),
    "block-new-map": ((8, 32, 128), None, None, "map"),
    "block-new-dtype": ((8, 40, 128), None, None, "dtype"),
    "gather-same": ((8, 48, 128), "gather", None, "nothing"),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_second_launch_compiles_only_a_new_signature(rng, case):
    shape, mode, ew, differs = CASES[case]
    m = af.transpose_map(shape)
    if mode is None:
        assert analyze_block_mode(m) is not None, "case must be block mode"
    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    m2, x2 = m, x
    if differs == "map":
        m2 = af.flip_map(shape, (0,))
        assert analyze_block_mode(m2) is not None
    elif differs == "dtype":
        x2 = x.astype(jnp.bfloat16)
    # ``ones`` for the epilogue operand compiles once per shape and dtype:
    # make it before counting
    _call(x2, m2, "gather", ew)

    first = _call(x, m, mode, ew)
    before = block_launch_cache_info()
    with count_compiles() as n:
        second = _call(x2, m2, mode, ew)
    after = block_launch_cache_info()

    hits = after.hits - before.hits
    misses = after.misses - before.misses
    if mode == "gather":
        assert (n[0], hits, misses) == (0, 0, 0)
    elif differs == "nothing":
        assert (n[0], hits, misses) == (0, 1, 0)
    else:
        assert n[0] >= 1 and (hits, misses) == (0, 1)
    if differs == "nothing":
        assert second.dtype == first.dtype
        np.testing.assert_array_equal(second, first)
    ref = _call(x2, m2, "gather", ew)
    np.testing.assert_array_equal(second, ref)


def test_compiled_program_block_phase_compiles_once(rng):
    shape = (8, 56, 128)

    def fn(a):
        return jnp.transpose(a, (1, 0, 2))

    x = jnp.asarray(rng.rand(*shape).astype(np.float32))
    compiled = tm_compile(fn, x)
    first, lowerings = compiled.run(x, backend="pallas")
    paths = [p for rep in lowerings for p in rep.paths()]
    assert paths == ["pallas.block"], paths
    before = block_launch_cache_info()
    with count_compiles() as n:
        second, _ = compiled.run(x, backend="pallas")
        second = np.asarray(second)
    after = block_launch_cache_info()
    assert n[0] == 0
    assert after.hits > before.hits and after.misses == before.misses
    np.testing.assert_array_equal(second, np.asarray(first))
    np.testing.assert_array_equal(second, np.asarray(fn(x)))


def test_traced_server_counts_block_launches(rng):
    from repro.obs import Tracer
    from repro.serving import ServerConfig, TMServer

    def fn(a):
        return jnp.transpose(a, (1, 0, 2))

    x = jnp.asarray(rng.rand(8, 64, 128).astype(np.float32))
    tr = Tracer()
    with TMServer(ServerConfig(backend="pallas", max_batch=1,
                               batch_timeout_s=0.001, trace=tr)) as srv:
        warm = srv.submit(fn, x, fn_key="tr").result(timeout=120)
        after_warm = dict(tr.counters())
        with count_compiles() as n:
            for _ in range(3):
                out = srv.submit(fn, x, fn_key="tr").result(timeout=120)
                np.testing.assert_array_equal(np.asarray(out),
                                              np.asarray(warm))
    counters = tr.counters()
    assert n[0] == 0
    assert counters["tmu/block_launch_misses"] == \
        after_warm["tmu/block_launch_misses"]
    assert counters["tmu/block_launch_hits"] >= \
        after_warm["tmu/block_launch_hits"] + 3
