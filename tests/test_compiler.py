"""repro.compiler: trace -> IR -> passes -> partition -> scheduled program.

Covers the PR acceptance criteria: the superres tail and an ESPCN block
compile end to end with >= 6 distinct jaxpr primitives matched, at least one
map-composition fusion and one epilogue sink fire (asserted on the pass
report), the scheduled program's cycle model shows pipelined latency below
unpipelined latency, and results are bit-exact vs the uncompiled function.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.compiler import tm_compile
from repro.compiler.passes import PassReport, run_pipeline
from repro.compiler.trace import graph_from_jaxpr
from repro.core import tm_ops
from repro.core.instr import TMOpcode
from repro.models import cnn


@pytest.fixture
def rng():
    return np.random.RandomState(7)


def _superres_inputs(rng, B=2, H=16, W=16, C=8, s=2):
    x = jnp.asarray(rng.rand(B, H, W, C).astype(np.float32))
    skip = jnp.asarray(rng.rand(B, H * s, W * s, C // (s * s))
                       .astype(np.float32))
    return x, skip


# ---------------------------------------------------------------------------
# acceptance
# ---------------------------------------------------------------------------

def test_acceptance_superres_and_cnn_block(rng):
    """>= 6 distinct matched primitives across the two flagship demos, with
    composition + epilogue sinking fired, pipelined < unpipelined, bit-exact."""
    x, skip = _superres_inputs(rng, H=24, W=24)
    c1 = tm_compile(cnn.superres_tail, x, skip)

    p = cnn.init_espcn(jax.random.PRNGKey(0), s=2)
    img = jnp.asarray(rng.rand(2, 12, 12, 3).astype(np.float32))
    c2 = tm_compile(lambda a: cnn.espcn(p, a), img)

    matched = c1.matched_prims | c2.matched_prims
    assert len(matched) >= 6, matched
    assert c1.pass_report.compositions >= 1, c1.pass_report.summary()
    assert c1.pass_report.epilogues_sunk >= 1, c1.pass_report.summary()

    pr = c1.partition_report
    assert pr.forwarded_cycles < pr.unpipelined_cycles
    assert pr.pipelined_cycles < pr.unpipelined_cycles

    ref1 = cnn.superres_tail(x, skip)
    ref2 = cnn.espcn(p, img)
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c1(x, skip, backend=backend)),
                              np.asarray(ref1)), backend
        assert np.array_equal(np.asarray(c2(img, backend=backend)),
                              np.asarray(ref2)), backend


def test_depth_to_space_composes_to_one_map(rng):
    """The reshape/transpose/reshape idiom must collapse into a single
    COARSE instruction whose map equals PixelShuffle semantics."""
    x, skip = _superres_inputs(rng)

    def d2s(a):
        # the (c, dy, dx) channel decomposition — exactly the paper's
        # PixelShuffle interleave, so the composed map must reproduce it
        B, H, W, C = a.shape
        h = a.reshape(B, H, W, C // 4, 2, 2)
        h = jnp.transpose(h, (0, 1, 4, 2, 5, 3))
        return h.reshape(B, H * 2, W * 2, C // 4)

    c = tm_compile(d2s, x)
    assert c.pass_report.compositions == 2
    tm = [i for p in c.tm_programs for i in p.instrs]
    assert len(tm) == 1 and tm[0].opcode == TMOpcode.COARSE
    got = c(x)
    assert np.array_equal(np.asarray(got),
                          np.asarray(tm_ops.pixel_shuffle(x, 2)))


# ---------------------------------------------------------------------------
# trace
# ---------------------------------------------------------------------------

def test_trace_matches_raw_primitives(rng):
    x, skip = _superres_inputs(rng)
    with_jaxpr = jax.make_jaxpr(cnn.superres_tail)(x, skip)
    graph = graph_from_jaxpr(with_jaxpr)
    assert {"reshape", "transpose", "add", "slice", "pad"} <= graph.matched_prims
    assert graph.tpu_nodes() == []  # the tail is pure tensor manipulation


def test_trace_leaves_compute_opaque(rng):
    p = cnn.init_espcn(jax.random.PRNGKey(0), s=2)
    img = jnp.asarray(rng.rand(1, 8, 8, 3).astype(np.float32))
    c = tm_compile(lambda a: cnn.espcn(p, a), img)
    prims = {n.primitive_name for n in c.graph.tpu_nodes()}
    assert "conv_general_dilated" in prims


def test_trace_tagged_tm_ops(rng):
    u = jnp.asarray(rng.rand(2, 6, 6, 8).astype(np.float32))
    sk = jnp.asarray(rng.rand(2, 12, 12, 4).astype(np.float32))
    c = tm_compile(cnn.yolo_neck, u, sk)
    assert {"tm_map", "concatenate"} <= c.matched_prims
    ref = cnn.yolo_neck(u, sk)
    assert np.array_equal(np.asarray(c(u, sk)), np.asarray(ref))


def test_trace_interleaving_reshape_stays_opaque(rng):
    x = jnp.asarray(rng.rand(6, 4).astype(np.float32))
    c = tm_compile(lambda a: a.reshape(8, 3), x)  # boundaries don't nest
    assert "reshape" not in c.matched_prims
    assert np.array_equal(np.asarray(c(x)), np.asarray(x.reshape(8, 3)))


def test_tagged_jaxpr_survives_jit_cache(rng):
    """Regression: tm_compile of a jit-wrapped fn caches the *tagged* jaxpr
    in jax's trace cache; the tagging primitives must lower under XLA so the
    later normal jit call still runs (and still matches)."""
    @jax.jit
    def f(a):
        return tm_ops.transpose(a) + 1.0

    x = jnp.asarray(rng.rand(2, 3, 4).astype(np.float32))
    c = tm_compile(f, x)
    ref = jnp.transpose(x, (1, 0, 2)) + 1.0
    assert np.array_equal(np.asarray(f(x)), np.asarray(ref))  # jit path
    assert np.array_equal(np.asarray(c(x)), np.asarray(ref))  # compiled path


def test_compile_rejects_wrong_dtype(rng):
    x, skip = _superres_inputs(rng)
    c = tm_compile(cnn.superres_tail, x, skip)
    with pytest.raises(TypeError):
        c(x.astype(jnp.int32), skip.astype(jnp.int32))


def test_compile_rejects_wrong_shape(rng):
    x, skip = _superres_inputs(rng)
    c = tm_compile(cnn.superres_tail, x, skip)
    bad = jnp.zeros((1, 3, 3, 8), jnp.float32)
    with pytest.raises(TypeError):
        c(bad, skip)


# ---------------------------------------------------------------------------
# passes
# ---------------------------------------------------------------------------

def test_copy_elim_removes_identity_slice(rng):
    x = jnp.asarray(rng.rand(4, 6).astype(np.float32))

    def f(a):
        b = jax.lax.slice(a, (0, 0), (4, 6))  # full-range slice: identity map
        return jnp.transpose(b, (1, 0))

    c = tm_compile(f, x)
    # the identity collapses — by composition or by copy elimination
    assert c.pass_report.copies_elided + c.pass_report.compositions >= 1
    assert sum(len(p.instrs) for p in c.tm_programs) == 1
    assert np.array_equal(np.asarray(c(x)), np.asarray(x.T))


def test_copy_elim_removes_copy_node(rng):
    x = jnp.asarray(rng.rand(4, 6, 2).astype(np.float32))

    def f(a):
        return jnp.flip(jnp.copy(a), axis=0)

    c = tm_compile(f, x)
    assert c.pass_report.copies_elided >= 1, c.pass_report.summary()
    assert np.array_equal(np.asarray(c(x)), np.asarray(f(x)))


def test_epilogue_sink_requires_available_operand(rng):
    """The elementwise operand must exist before the coarse instr issues;
    an operand produced *after* the producer cannot sink."""
    x = jnp.asarray(rng.rand(4, 6, 2).astype(np.float32))

    def f(a):
        t = jnp.transpose(a, (1, 0, 2))     # coarse producer
        r = jnp.flip(jnp.transpose(a, (1, 0, 2)), axis=0)  # later producer
        return t + r

    c = tm_compile(f, x)
    ref = f(x)
    assert np.array_equal(np.asarray(c(x)), np.asarray(ref))


def test_sub_epilogue_only_streams_lhs(rng):
    x = jnp.asarray(rng.rand(4, 6, 2).astype(np.float32))
    skip = jnp.asarray(rng.rand(6, 4, 2).astype(np.float32))

    def f(a, s):
        return s - jnp.transpose(a, (1, 0, 2))  # transpose is rhs of sub

    c = tm_compile(f, x, skip)
    # sub is not commutative: the coarse result on the rhs must NOT sink
    assert c.pass_report.epilogues_sunk == 0
    assert np.array_equal(np.asarray(c(x, skip)), np.asarray(f(x, skip)))


def test_compose_preserves_pad_fill_through_reshape(rng):
    """Regression: composing a split-bearing reshape over a pad used to take
    the outer map's fill register, zeroing the pad constant."""
    x = jnp.asarray(rng.rand(2, 3).astype(np.float32))

    def f(a):
        h = jnp.pad(a, ((1, 1), (1, 1)), constant_values=5.0)
        return h.reshape(2, 10)

    c = tm_compile(f, x)
    assert c.pass_report.compositions == 1, c.pass_report.summary()
    ref = f(x)
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(x, backend=backend)),
                              np.asarray(ref)), backend


def test_rme_legalize_pins_batch_dims(rng):
    pred = jnp.asarray(rng.rand(3, 40, 6).astype(np.float32))
    c = tm_compile(lambda p: cnn.detect_tail(p, 10.0, 8), pred)
    assert c.pass_report.rme_legalized == 1
    fine = [n.instr for n in c.graph.tm_nodes()
            if n.instr.opcode == TMOpcode.FINE_EVALUATE]
    assert fine and fine[0].meta["batch_dims"] == 1
    # and the batched kernel actually claims it on the pallas backend
    ref = cnn.detect_tail(pred, 10.0, 8)
    got = c(pred, backend="pallas")
    assert np.array_equal(np.asarray(got), np.asarray(ref))
    paths = [r.path for rep in c.last_lowering for r in rep.records]
    assert "pallas.rme.evaluate" in paths, paths


# ---------------------------------------------------------------------------
# partition + allocation
# ---------------------------------------------------------------------------

def test_partition_alternates_phases(rng):
    p = cnn.init_espcn(jax.random.PRNGKey(0), s=2)
    img = jnp.asarray(rng.rand(1, 8, 8, 3).astype(np.float32))
    c = tm_compile(lambda a: cnn.espcn(p, a), img)
    kinds = [ph.kind for ph in c.partition_report.phases]
    assert "tpu" in kinds and "tmu" in kinds
    for ph in c.partition_report.tmu_phases:
        assert ph.program is not None and ph.schedule is not None


def test_scratch_allocation_reuses_slots(rng):
    x, skip = _superres_inputs(rng, H=24, W=24)
    c = tm_compile(cnn.superres_tail, x, skip)
    plan = c.scratch_plan
    assert plan.total_bytes <= plan.naive_bytes
    # forwarded intermediates are held at two-segment granularity
    assert plan.streamed, "expected streamed buffers on the forwarded edges"
    for name in plan.streamed:
        assert name in plan.slot_of


def test_pass_report_summary_prints_pipeline(rng):
    x, skip = _superres_inputs(rng)
    c = tm_compile(cnn.superres_tail, x, skip)
    text = c.report()
    for token in ("compose-maps", "epilogue-sink", "phases", "scratch"):
        assert token in text, text


# ---------------------------------------------------------------------------
# dynamic_slice matching (constant starts)
# ---------------------------------------------------------------------------

def test_dynamic_slice_constant_starts_matches(rng):
    x = jnp.asarray(rng.rand(5, 7, 3).astype(np.float32))
    fn = lambda a: jax.lax.dynamic_slice(a, (1, 2, 0), (2, 3, 3))
    c = tm_compile(fn, x)
    assert "dynamic_slice" in c.matched_prims
    (node,) = [n for n in c.graph.nodes if n.kind == "tmu"]
    assert node.instr.opcode == TMOpcode.COARSE
    assert len(node.instr.srcs) == 1  # start operands folded into the map
    for backend in ("reference", "fused", "pallas"):
        got = c(x, backend=backend)
        assert np.array_equal(np.asarray(got), np.asarray(fn(x))), backend


def test_dynamic_slice_clamps_out_of_range_starts(rng):
    # lax clamps start 4 -> 3 (=5-2) and 6 -> 4 (=7-3); the map must agree
    x = jnp.asarray(rng.rand(5, 7, 3).astype(np.float32))
    fn = lambda a: jax.lax.dynamic_slice(a, (4, 6, 0), (2, 3, 3))
    c = tm_compile(fn, x)
    assert "dynamic_slice" in c.matched_prims
    assert np.array_equal(np.asarray(c(x)), np.asarray(fn(x)))


def test_dynamic_slice_traced_start_stays_opaque(rng):
    x = jnp.asarray(rng.rand(5, 7, 3).astype(np.float32))
    fn = lambda a, i: jax.lax.dynamic_slice(a, (i, 0, 0), (2, 3, 3))
    c = tm_compile(fn, x, jnp.int32(1))
    assert "dynamic_slice" not in c.matched_prims  # runtime start: TPU node
    assert np.array_equal(np.asarray(c(x, jnp.int32(1))),
                          np.asarray(fn(x, jnp.int32(1))))


def test_dynamic_slice_traced_start_leaves_pass_report_note(rng):
    # the fallback must explain itself: the trace note rides the pass report
    # (and never raises mid-trace), and execution stays bit-exact on the
    # stream-dispatched path too
    x = jnp.asarray(rng.rand(5, 7, 3).astype(np.float32))
    fn = lambda a, i: jax.lax.dynamic_slice(a, (i, 0, 0), (2, 3, 3)) * 2.0
    c = tm_compile(fn, x, jnp.int32(2))
    assert c.pass_report.trace_fallbacks == 1
    (note,) = [a.detail for a in c.pass_report.actions
               if a.pass_name == "trace-fallback"]
    assert "dynamic_slice" in note and "non-constant start" in note
    assert "trace-fallback" in c.pass_report.summary()
    assert c.graph.notes == [note]
    from repro.runtime.streams import StreamRuntime
    with StreamRuntime() as rt:
        got, _ = c.run(x, jnp.int32(2), runtime=rt)
    assert np.array_equal(np.asarray(got), np.asarray(fn(x, jnp.int32(2))))


def test_traced_dynamic_slice_does_not_trigger_pjit_inlining(rng):
    # a jitted block whose only TM-shaped eqn is a dynamic_slice with a
    # traced start must stay one opaque TPU node (no per-eqn explosion)
    x = jnp.asarray(rng.rand(6, 6).astype(np.float32))

    @jax.jit
    def inner(a, i):
        h = jnp.dot(a, a)  # opaque compute, no other matchable eqns
        return jax.lax.dynamic_slice(h, (i, 0), (2, 6))

    c = tm_compile(lambda a, i: inner(a, i) + 0.0, x, jnp.int32(1))
    assert "dynamic_slice" not in c.matched_prims
    kinds = [n.kind for n in c.graph.nodes]
    # the jit stayed one opaque node (+ the outer scalar add): no explosion
    assert kinds == ["tpu", "tpu"], kinds
    got = c(x, jnp.int32(1))
    assert np.array_equal(np.asarray(got),
                          np.asarray(inner(x, jnp.int32(1)) + 0.0))


# ---------------------------------------------------------------------------
# dynamic_slice clamp semantics: differential vs lax (negative / past-the-end)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("starts", [(-2, -1, 0), (9, 12, 1), (-3, 6, 2)])
def test_dynamic_slice_clamp_differential_vs_lax(rng, starts):
    # the fold max(0, min(st, dim - sz)) must agree with lax.dynamic_slice's
    # own clamp for negative AND past-the-end constant starts, on all three
    # backends — a divergence here silently corrupts every bucketed decode
    x = jnp.asarray(rng.rand(5, 7, 3).astype(np.float32))
    fn = lambda a: jax.lax.dynamic_slice(a, starts, (2, 3, 1))
    c = tm_compile(fn, x)
    assert "dynamic_slice" in c.matched_prims
    ref = np.asarray(fn(x))
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(x, backend=backend)), ref), \
            (backend, starts)


# ---------------------------------------------------------------------------
# dynamic_update_slice matching (KV-cache append)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pos", [0, 5, 13])
def test_update_slice_kv_append_round_trip(rng, pos):
    """Constant-position KV append: matched as an overlay Route, bit-exact
    vs lax.dynamic_update_slice on all three backends."""
    cache = jnp.asarray(rng.rand(2, 16, 2, 4).astype(np.float32))
    upd = jnp.asarray(rng.rand(2, 3, 2, 4).astype(np.float32))
    fn = lambda c_, u: jax.lax.dynamic_update_slice(c_, u, (0, pos, 0, 0))
    c = tm_compile(fn, cache, upd)
    assert "dynamic_update_slice" in c.matched_prims
    (node,) = [n for n in c.graph.nodes if n.kind == "tmu"]
    assert node.instr.opcode == TMOpcode.COARSE
    assert node.instr.meta and node.instr.meta.get("overlay") is True
    assert len(node.instr.srcs) == 2  # operand + update; starts in the maps
    ref = np.asarray(fn(cache, upd))
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(cache, upd, backend=backend)),
                              ref), backend


def test_update_slice_clamps_past_the_end_start(rng):
    # lax clamps start 14 -> 13 (=16-3); the overlay window must agree
    cache = jnp.asarray(rng.rand(1, 16, 4).astype(np.float32))
    upd = jnp.asarray(rng.rand(1, 3, 4).astype(np.float32))
    fn = lambda c_, u: jax.lax.dynamic_update_slice(c_, u, (0, 14, 0))
    c = tm_compile(fn, cache, upd)
    assert "dynamic_update_slice" in c.matched_prims
    assert np.array_equal(np.asarray(c(cache, upd)),
                          np.asarray(fn(cache, upd)))


def test_update_slice_traced_start_degrades_with_note(rng):
    """A runtime start must degrade to an opaque TPU phase with a
    trace-fallback note — never an exception — mirroring dynamic_slice."""
    cache = jnp.asarray(rng.rand(1, 16, 4).astype(np.float32))
    upd = jnp.asarray(rng.rand(1, 3, 4).astype(np.float32))
    fn = lambda c_, u, i: jax.lax.dynamic_update_slice(c_, u, (0, i, 0)) * 2.0
    c = tm_compile(fn, cache, upd, jnp.int32(5))
    assert "dynamic_update_slice" not in c.matched_prims
    assert c.pass_report.trace_fallbacks == 1
    (note,) = [a.detail for a in c.pass_report.actions
               if a.pass_name == "trace-fallback"]
    assert "dynamic_update_slice" in note and "non-constant start" in note
    assert "bucket the position" in note
    got = c(cache, upd, jnp.int32(5))
    assert np.array_equal(np.asarray(got),
                          np.asarray(fn(cache, upd, jnp.int32(5))))


# ---------------------------------------------------------------------------
# gather matching (embedding row fetch / token dispatch)
# ---------------------------------------------------------------------------

def test_gather_arithmetic_progression_matches_single_map(rng):
    x = jnp.asarray(rng.rand(10, 6).astype(np.float32))
    idx = jnp.asarray([1, 3, 5, 7])
    fn = lambda a: jnp.take(a, idx, axis=0)
    c = tm_compile(fn, x)
    assert "gather" in c.matched_prims
    (node,) = [n for n in c.graph.nodes if n.kind == "tmu"]
    assert node.instr.maps is None  # one strided map, not a band Route
    ref = np.asarray(fn(x))
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(x, backend=backend)), ref), backend


def test_gather_irregular_indices_match_band_route(rng):
    x = jnp.asarray(rng.rand(10, 6).astype(np.float32))
    idx = jnp.asarray([3, 0, 7, 7, 2])  # irregular, with a repeat
    fn = lambda a: jnp.take(a, idx, axis=0)
    c = tm_compile(fn, x)
    assert "gather" in c.matched_prims
    (node,) = [n for n in c.graph.nodes if n.kind == "tmu"]
    assert node.instr.maps is not None and len(node.instr.maps) == 5
    ref = np.asarray(fn(x))
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(x, backend=backend)), ref), backend


def test_gather_inner_axis_matches(rng):
    x = jnp.asarray(rng.rand(4, 9, 3).astype(np.float32))
    idx = jnp.asarray([8, 1, 4])
    fn = lambda a: jnp.take(a, idx, axis=1)
    c = tm_compile(fn, x)
    assert "gather" in c.matched_prims
    assert np.array_equal(np.asarray(c(x)), np.asarray(fn(x)))


def test_gather_traced_indices_degrade_with_note(rng):
    x = jnp.asarray(rng.rand(10, 6).astype(np.float32))
    idx = jnp.asarray([3, 0, 7])
    fn = lambda a, i: jnp.take(a, i, axis=0) * 2.0
    c = tm_compile(fn, x, idx)
    assert "gather" not in c.matched_prims
    notes = [a.detail for a in c.pass_report.actions
             if a.pass_name == "trace-fallback"]
    assert any("traced index vector" in n for n in notes), notes
    assert np.array_equal(np.asarray(c(x, idx)), np.asarray(fn(x, idx)))


def test_gather_too_many_irregular_indices_degrades(rng):
    from repro.compiler.trace import _GATHER_MAX_BANDS
    n = _GATHER_MAX_BANDS + 1
    x = jnp.asarray(rng.rand(200, 3).astype(np.float32))
    vals = rng.randint(0, 200, size=n)
    vals[1] = vals[0] + 7  # break any accidental arithmetic progression
    vals[2] = vals[0]
    idx = jnp.asarray(vals)
    fn = lambda a: jnp.take(a, idx, axis=0)
    c = tm_compile(fn, x)
    assert "gather" not in c.matched_prims
    notes = [a.detail for a in c.pass_report.actions
             if a.pass_name == "trace-fallback"]
    assert any("band Route budget" in m for m in notes), notes
    assert np.array_equal(np.asarray(c(x)), np.asarray(fn(x)))


# ---------------------------------------------------------------------------
# reduce_window: identity/strided layouts match, real pooling stays opaque
# ---------------------------------------------------------------------------

def test_reduce_window_degenerate_stride_matches(rng):
    x = jnp.asarray(rng.rand(4, 8, 6).astype(np.float32))
    fn = lambda a: jax.lax.reduce_window(
        a, -jnp.inf, jax.lax.max, (1, 1, 1), (1, 2, 3), "VALID")
    c = tm_compile(fn, x)
    assert "reduce_window_max" in c.matched_prims
    ref = np.asarray(fn(x))
    for backend in ("reference", "fused", "pallas"):
        assert np.array_equal(np.asarray(c(x, backend=backend)), ref), backend


def test_reduce_window_real_pooling_stays_opaque(rng):
    x = jnp.asarray(rng.rand(1, 8, 8, 2).astype(np.float32))
    fn = lambda a: jax.lax.reduce_window(
        a, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
    c = tm_compile(fn, x)
    assert "reduce_window_max" not in c.matched_prims
    # genuine reductions are compute: no fallback noise either
    assert c.pass_report.trace_fallbacks == 0
    assert np.array_equal(np.asarray(c(x)), np.asarray(fn(x)))


# ---------------------------------------------------------------------------
# phase defragmentation
# ---------------------------------------------------------------------------

def test_phase_defrag_moves_singleton_past_independent_tpu(rng):
    """A singleton TM node wedged between TPU nodes that neither read its
    output nor feed it must migrate to join the nearest TM run."""
    a = jnp.asarray(rng.rand(6, 6).astype(np.float32))
    b = jnp.asarray(rng.rand(4, 4).astype(np.float32))

    def fn(a, b):
        t = (a @ a).T          # TM singleton wedged after the dot
        r = jnp.tanh(b).T      # independent chain: TPU then TM
        return t, r

    c = tm_compile(fn, a, b)
    assert c.pass_report.phases_defragmented >= 1, c.pass_report.summary()
    mix = c.partition_report.phase_mix()
    assert mix["tmu_singletons"] == 0, mix
    assert mix["tmu_phases"] == 1, mix
    got = c(a, b)
    ref = fn(a, b)
    for g, w in zip(got, ref):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_phase_defrag_respects_data_dependence(rng):
    # the intervening TPU node READS the singleton's output: no legal move
    a = jnp.asarray(rng.rand(6, 6).astype(np.float32))

    def fn(a):
        h = a @ a
        t = h.T                # singleton
        u = jnp.tanh(t)        # reads t: blocks the forward move
        return u[:2]           # TM (slice) after the blocker

    c = tm_compile(fn, a)
    # order must stay valid regardless of whether any move was found
    assert np.array_equal(np.asarray(c(a)), np.asarray(fn(a)))
    names_in_order = [n.kind for n in c.graph.nodes]
    assert names_in_order.index("tmu") > 0  # transpose still after the dot


def test_phase_mix_reports_fragmentation(rng):
    x, skip = _superres_inputs(rng)
    c = tm_compile(cnn.superres_tail, x, skip)
    mix = c.partition_report.phase_mix()
    assert mix["phases"] == mix["tpu_phases"] + mix["tmu_phases"]
    assert len(mix["kinds"]) == mix["phases"]
    assert mix["tmu_instrs"] >= mix["tmu_phases"]


# ---------------------------------------------------------------------------
# exact mode: per-eqn TPU evaluation matches eager bit for bit
# ---------------------------------------------------------------------------

def test_exact_mode_matches_eager_through_mean_rsqrt_chain(rng):
    """The decode-path divergence, pinned: eager jnp code bakes constants
    into each dispatched computation (div-by-const becomes mul-by-recip) and
    dispatches op by op; whole-phase jit lets XLA rewrite across the fused
    rsqrt(x/c + eps) chain.  exact=True must reproduce eager bit for bit."""
    g = jnp.asarray(rng.rand(48).astype(np.float32))
    x = jnp.asarray(rng.randn(2, 8, 48).astype(np.float32))

    def fn(x):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * g

    c = tm_compile(fn, x)
    ref = np.asarray(fn(x))
    got = np.asarray(c(x, exact=True))
    assert np.array_equal(got, ref)
