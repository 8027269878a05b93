"""Weight-only TM work above the trace-time fold limit is hoisted.

A TM eqn whose operands are all graph consts (a closed-over weight's
``.T``) folds once at compile time into a graph const instead of running
as a TM phase on every call, and programs compiled over the same weight
share one folded array.  Constants here are 1100x1024 float32, just above
``trace._CONST_FOLD_LIMIT`` (1M elements)."""

import gc
import os
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compiler import tm_compile
from repro.compiler import trace
from repro.obs.tracer import Tracer

ROWS, COLS = 1100, 1024


def _weight(seed=0, shape=(ROWS, COLS)):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32))


def _x(rows=2):
    return jnp.asarray(np.random.RandomState(1).randn(rows, COLS)
                       .astype(np.float32))


def _hoisted_consts(compiled):
    return [compiled.graph.consts[h.dst] for h in compiled.graph.hoisted]


def _tm_matched(compiled):
    return [n.matched for n in compiled.graph.tm_nodes()]


@pytest.mark.parametrize("backend,exact", [
    ("reference", False), ("fused", False), ("pallas", False),
    ("reference", True)])
def test_weight_transpose_is_hoisted_and_bit_exact(backend, exact):
    w = _weight()

    def f(x):
        y = x @ w.T                       # the const transpose: hoisted
        return y.reshape(2, 2, ROWS // 2).transpose(0, 2, 1)  # stays TM

    x = _x()
    compiled = tm_compile(f, x)
    assert compiled.pass_report.consts_hoisted == 1
    assert "const-hoist" in compiled.pass_report.summary()
    (h,) = compiled.graph.hoisted
    assert (h.prim, h.nbytes, h.shared) == ("transpose", ROWS * COLS * 4,
                                            False)
    # the const transpose left the graph; the transpose of the dot's
    # result (a graph input's descendant) is still a TM node
    assert _tm_matched(compiled) == ["transpose"]
    assert compiled.graph.buffers[h.dst].shape == (COLS, ROWS)
    np.testing.assert_array_equal(np.asarray(_hoisted_consts(compiled)[0]),
                                  np.asarray(w).T)
    out = compiled(x, backend=backend, exact=exact)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(f(x)))


def test_programs_over_one_weight_share_one_fold_and_the_memo_lets_go():
    w = _weight(seed=2)
    wref, wid = weakref.ref(w), id(w)
    f1 = lambda x: x @ w.T               # noqa: E731
    f2 = lambda x: jnp.tanh(x @ w.T)     # noqa: E731
    c1, c2 = tm_compile(f1, _x()), tm_compile(f2, _x(rows=3))
    (a,), (b,) = _hoisted_consts(c1), _hoisted_consts(c2)
    assert a is b
    assert [h.shared for h in c1.graph.hoisted + c2.graph.hoisted] == \
        [False, True]
    assert sum(wid in k[2] for k in trace._HOISTED) == 1
    del w, f1, f2, c1, c2, a, b
    gc.collect()
    assert wref() is None
    assert not any(wid in k[2] for k in trace._HOISTED)


def test_hoist_counters_count_one_fold_and_one_shared_hit():
    w = _weight(seed=3)
    tracer = Tracer()
    tm_compile(lambda x: x @ w.T, _x(), tracer=tracer)
    tm_compile(lambda x: (x @ w.T) * 2.0, _x(rows=4), tracer=tracer)
    counters = tracer.counters()
    assert counters["compile/const_hoist"] == 1
    assert counters["compile/const_hoist_shared"] == 1
    spans = tracer.spans(prefix="compile/trace")
    assert [s.arg("const_hoist_bytes") for s in spans] == \
        [ROWS * COLS * 4] * 2


def test_concurrent_compiles_fold_one_copy():
    """More compiling threads than cores, switching often: the lock lets
    exactly one fold the weight, and every other program shares it."""
    w = _weight(seed=4)
    tracer = Tracer()
    n = (os.cpu_count() or 1) + 2
    barrier = threading.Barrier(n)
    results = [None] * n

    def compile_one(i):
        barrier.wait()
        results[i] = tm_compile(lambda x: x @ w.T, _x(rows=i + 1),
                                tracer=tracer)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=compile_one, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    folded = [_hoisted_consts(c)[0] for c in results]
    assert all(f is folded[0] for f in folded)
    assert tracer.counters()["compile/const_hoist"] == 1
    assert tracer.counters()["compile/const_hoist_shared"] == n - 1


def _graph_input_transpose():
    return (lambda x: x.T), (jnp.ones((ROWS, COLS), jnp.float32),)


def _literal_broadcast():
    # a broadcast of a scalar literal and an iota: generators, no weight
    def f(x):
        ones = jnp.broadcast_to(jnp.float32(1.0), (ROWS, COLS))
        ramp = jnp.arange(ROWS * COLS, dtype=jnp.float32).reshape(ROWS, COLS)
        return x + ones + ramp
    return f, (jnp.ones((ROWS, COLS), jnp.float32),)


def _inflating_broadcast():
    v = _weight(seed=5, shape=(COLS,))
    m = _weight(seed=6, shape=(1, 400, COLS))
    return ((lambda x: x + jnp.broadcast_to(v, (ROWS, COLS)),
             lambda y: y + jnp.broadcast_to(m, (3, 400, COLS))),
            ((jnp.ones((ROWS, COLS), jnp.float32),),
             (jnp.ones((3, 400, COLS), jnp.float32),)))


@pytest.mark.parametrize("case", ["graph_input", "literals_only",
                                  "inflating_broadcast"])
def test_nothing_else_is_hoisted(case):
    if case == "inflating_broadcast":
        fns, argss = _inflating_broadcast()
    else:
        fn, args = (_graph_input_transpose() if case == "graph_input"
                    else _literal_broadcast())
        fns, argss = (fn,), (args,)
    for fn, args in zip(fns, argss):
        compiled = tm_compile(fn, *args)
        assert compiled.pass_report.consts_hoisted == 0
        assert compiled.graph.hoisted == []
        if case != "literals_only":
            assert _tm_matched(compiled), compiled.report()
        np.testing.assert_array_equal(np.asarray(compiled(*args)),
                                      np.asarray(fn(*args)))


def test_below_the_limit_consts_fold_as_before():
    w = _weight(seed=7, shape=(64, COLS))
    compiled = tm_compile(lambda x: x @ w.T, _x())
    assert compiled.pass_report.consts_hoisted == 0
    assert compiled.graph.tm_nodes() == []


def test_folded_const_is_never_donated():
    w = _weight(seed=8)

    def f(x):
        h = jnp.tanh(x)
        return (h @ w.T) + 1.0

    compiled = tm_compile(f, _x())
    (h,) = compiled.graph.hoisted
    phases = compiled.partition_report.phases
    assert any(h.dst in ph.reads for ph in phases)
    for ph in phases:
        if ph.kind == "tpu":
            donated = {ph.reads[i] for i in compiled._donatable(ph)}
            assert h.dst not in donated


def test_served_decode_hoists_the_tied_unembedding_once():
    """A decode session whose tied embedding is above the fold limit
    (32768x48): every position's program reads one transposed copy, and
    the served tokens and logits stay bit-exact against the eager steps."""
    import dataclasses

    from repro.configs.phi4_mini_3p8b import smoke_config
    from repro.serving import DecodeSession, ServerConfig
    cfg = dataclasses.replace(smoke_config(), vocab=32768)
    prompts = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    config = ServerConfig(max_batch=4, batch_timeout_s=0.0,
                          cache_capacity=24, exact=True, trace=True)
    with DecodeSession(cfg, max_len=16, config=config) as sess:
        toks, logits = sess.generate(prompts, 3)
        ref_toks, ref_logits = sess.reference_generate(prompts, 3)
        counters = sess.server.tracer.counters()
        entries = sess.server.snapshot_stats()["cache"]["entries"]
    assert bool(jnp.array_equal(toks, ref_toks))
    for g, w in zip(logits, ref_logits):
        assert bool(jnp.array_equal(g, w))
    # prefill and each decode position compile a program: one fold, and
    # every later program reads it
    assert entries >= 3
    assert counters["compile/const_hoist"] == 1
    assert counters["compile/const_hoist_shared"] == entries - 1


def test_a_mutable_numpy_weight_is_hoisted_but_not_shared():
    w = np.asarray(_weight(seed=9))
    f = lambda x: x @ jnp.transpose(w)   # noqa: E731
    tracer = Tracer()
    c1 = tm_compile(f, _x(), tracer=tracer)
    c2 = tm_compile(f, _x(rows=3), tracer=tracer)
    assert c1.pass_report.consts_hoisted == c2.pass_report.consts_hoisted == 1
    (a,), (b,) = _hoisted_consts(c1), _hoisted_consts(c2)
    assert a is not b
    assert tracer.counters()["compile/const_hoist"] == 2
    assert tracer.counters().get("compile/const_hoist_shared", 0) == 0
    np.testing.assert_array_equal(np.asarray(c1(_x())), np.asarray(f(_x())))
