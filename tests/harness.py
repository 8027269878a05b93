"""Differential-testing harness: every paper operator as a TMProgram, run
through all executor backends and checked for agreement.

The harness is the safety net under the kernel-dispatch rewiring: each
:class:`OpCase` builds a single-purpose program, and :func:`run_differential`
executes it through the ``reference``, ``fused`` and ``pallas`` backends,
asserting

  * bit-exact agreement for integer dtypes and for pure data-movement float
    ops (gathers never touch values), atol-bounded agreement for arithmetic
    ops (resize);
  * an invariant pallas lowering report — tests pin *which* datapath ran
    (block-mode DMA, gather kernel, RME compaction, fallback), across all
    dtypes, so a silent fallback is a test failure, not a perf mystery.

Shapes are deliberately odd / non-tile-aligned where the op permits, to
exercise the kernels' remainder handling.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np
import jax.numpy as jnp

from repro.core import affine as af
from repro.core.executor import TMExecutor
from repro.core.instr import EwOp, RMEConfig, TMInstr, TMOpcode, TMProgram

ALL_DTYPES = ("int8", "int32", "bfloat16", "float32")
FLOAT_DTYPES = ("bfloat16", "float32")
BACKENDS = ("reference", "fused", "pallas")


@dataclasses.dataclass(frozen=True)
class OpCase:
    """One paper operator expressed as a (program, input shapes) builder."""

    name: str
    build: Callable[[], tuple[TMProgram, dict[str, tuple[int, ...]]]]
    expect_paths: tuple[str, ...]       # pallas lowering at batch_dims=0
    dtypes: tuple[str, ...] = ALL_DTYPES
    supports_batch: bool = True
    exact: bool = True                  # bit-exact across backends
    atol: float = 0.0                   # used when exact=False (float dtypes)
    mask_inputs: tuple[str, ...] = ()   # inputs that must be boolean
    scale: float = 100.0                # float payload range (thresholds are
    #                                     integer-valued; arithmetic ops use
    #                                     1.0 so atol is meaningful)


def _single(name, m, **kw):
    return TMProgram([TMInstr(TMOpcode.COARSE, ("x",), "y", map_=m, **kw)],
                     inputs=("x",), outputs=("y",)), {"x": m.in_shape}


def _transpose():
    return _single("transpose", af.transpose_map((5, 7, 3)))


def _rot90():
    return _single("rot90", af.rot90_map((5, 7, 3)))


def _pixel_shuffle():
    return _single("ps", af.pixel_shuffle_map((6, 10, 8), 2))


def _pixel_unshuffle():
    return _single("pu", af.pixel_unshuffle_map((6, 10, 2), 2))


def _upsample():
    return _single("us", af.upsample_map((5, 7, 3), 2))


def _split():
    return _single("split", af.split_map((5, 7, 6), 3, 1))


def _strided_slice():
    m = af.strided_slice_map((5, 7, 3), (1, 2, 0), (2, 3, 1), (2, 2, 3))
    return _single("slice", m)


def _rearrange():
    return _single("rearrange", af.rearrange_map((6, 8, 3), 4, 16))


def _img2col():
    m = af.img2col_map((8, 9, 3), 3, 3, 1, 1)
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x",), "y", map_=m,
                 meta={"img2col": {"kh": 3, "kw": 3, "stride": 1, "pad": 1}})],
        inputs=("x",), outputs=("y",))
    return prog, {"x": (8, 9, 3)}


def _route():
    maps = tuple(af.route_maps([(5, 7, 2), (5, 7, 3)]))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("a", "b"), "y", maps=maps)],
        inputs=("a", "b"), outputs=("y",))
    return prog, {"a": (5, 7, 2), "b": (5, 7, 3)}


def _add():
    # paper Add: identity layout map + element-wise stage in one instruction
    m = af.identity_map((5, 7, 3))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x", "r"), "y", map_=m, ew=EwOp.ADD)],
        inputs=("x", "r"), outputs=("y",))
    return prog, {"x": (5, 7, 3), "r": (5, 7, 3)}


def _bboxcal():
    prog = TMProgram(
        [TMInstr(TMOpcode.FINE_EVALUATE, ("p",), "y",
                 rme=RMEConfig(scheme="evaluate", threshold=10.0, cmp="ge",
                               score_index=4, capacity=8))],
        inputs=("p",), outputs=("y",))
    return prog, {"p": (33, 7)}


def _assemble_runtime():
    prog = TMProgram(
        [TMInstr(TMOpcode.FINE_ASSEMBLE, ("x", "mask"), "y",
                 rme=RMEConfig(scheme="assemble", capacity=8))],
        inputs=("x", "mask"), outputs=("y",))
    return prog, {"x": (21, 5), "mask": (21,)}


def _assemble_static():
    prog = TMProgram(
        [TMInstr(TMOpcode.FINE_ASSEMBLE, ("x",), "y",
                 rme=RMEConfig(scheme="assemble",
                               lane_mask=(1, 0, 1, 1, 0, 0, 1)))],
        inputs=("x",), outputs=("y",))
    return prog, {"x": (5, 7)}


def _resize():
    prog = TMProgram(
        [TMInstr(TMOpcode.RESIZE, ("x",), "y",
                 meta={"out_h": 11, "out_w": 5})],
        inputs=("x",), outputs=("y",))
    return prog, {"x": (6, 9, 3)}


def _chain():
    m1 = af.transpose_map((4, 6, 8))
    m2 = af.split_map((6, 4, 8), 2, 1)
    m3 = af.transpose_map((6, 4, 4))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x",), "a", map_=m1),
         TMInstr(TMOpcode.COARSE, ("a",), "b", map_=m2),
         TMInstr(TMOpcode.COARSE, ("b",), "y", map_=m3)],
        inputs=("x",), outputs=("y",))
    return prog, {"x": (4, 6, 8)}


CASES = [
    OpCase("transpose", _transpose, ("pallas.block",)),
    # a reversal inside a (8, 128) tile has no Mosaic lowering: row gather
    OpCase("rot90", _rot90, ("pallas.gather",)),
    OpCase("pixelshuffle", _pixel_shuffle, ("pallas.gather",)),
    OpCase("pixelunshuffle", _pixel_unshuffle, ("pallas.gather",)),
    OpCase("upsample", _upsample, ("pallas.gather",)),
    # a lane-offset channel slice is no tile-aligned DMA: row gather
    OpCase("split", _split, ("pallas.gather",)),
    OpCase("strided_slice", _strided_slice, ("pallas.gather",)),
    OpCase("rearrange", _rearrange, ("pallas.gather",)),
    OpCase("img2col", _img2col, ("pallas.img2col",)),
    OpCase("route", _route, ("pallas.route",)),
    OpCase("add", _add, ("pallas.block+ew",)),
    OpCase("bboxcal", _bboxcal, ("pallas.rme.evaluate",)),
    OpCase("assemble", _assemble_runtime, ("pallas.rme.assemble",),
           mask_inputs=("mask",)),
    OpCase("assemble_static", _assemble_static, ("reference.fine_asm",),
           supports_batch=False),
    OpCase("resize", _resize, ("pallas.resize",), dtypes=FLOAT_DTYPES,
           exact=False, atol=1e-5, scale=1.0),
    OpCase("chain", _chain,
           ("pallas.block", "pallas.gather", "pallas.block")),
]

CASES_BY_NAME = {c.name: c for c in CASES}


def make_inputs(case: OpCase, shapes: dict, dtype: str, batch_dims: int,
                rng: np.random.RandomState) -> dict[str, jnp.ndarray]:
    batch = tuple(range(2, 2 + batch_dims))  # (2,), (2, 3), ...
    bufs = {}
    for name, core in shapes.items():
        shape = batch + tuple(core)
        if name in case.mask_inputs:
            bufs[name] = jnp.asarray(rng.rand(*shape) > 0.5)
        elif dtype.startswith("int"):
            lo, hi = (-100, 100) if dtype != "int8" else (-99, 100)
            bufs[name] = jnp.asarray(
                rng.randint(lo, hi, size=shape).astype(dtype))
        else:
            # default scale ~[0, 100) so integer-valued thresholds discriminate
            bufs[name] = jnp.asarray(
                (rng.rand(*shape) * case.scale).astype(np.float32)).astype(dtype)
    return bufs


def assert_agree(case: OpCase, a: dict, b: dict, pair: str) -> None:
    for k in a:
        x = np.asarray(a[k], dtype=np.float64)
        y = np.asarray(b[k], dtype=np.float64)
        assert x.shape == y.shape, (case.name, pair, k, x.shape, y.shape)
        if case.exact:
            assert np.array_equal(x, y), (case.name, pair, k)
        else:
            np.testing.assert_allclose(x, y, atol=case.atol, rtol=0,
                                       err_msg=f"{case.name}:{pair}:{k}")


def run_differential(case: OpCase, dtype: str, batch_dims: int,
                     rng: np.random.RandomState):
    """Execute one case through every backend; return the pallas lowering.

    The chain-fused pallas executor rides along on every case: where the
    program has forwardable chains they execute as megakernels, where it
    has none the path is identical — either way the outputs must agree."""
    prog, shapes = case.build()
    bufs = make_inputs(case, shapes, dtype, batch_dims, rng)
    results = {}
    executors = {b: TMExecutor(backend=b) for b in BACKENDS}
    executors["pallas+chains"] = TMExecutor(backend="pallas",
                                            fuse_chains=True)
    for b, ex in executors.items():
        results[b] = ex(prog, bufs, batch_dims=batch_dims)
    assert_agree(case, results["reference"], results["fused"], "ref/fused")
    assert_agree(case, results["reference"], results["pallas"], "ref/pallas")
    assert_agree(case, results["pallas"], results["pallas+chains"],
                 "pallas/chained")
    return executors["pallas"].last_lowering


# ---------------------------------------------------------------------------
# chain cases: programs with forwardable producer→consumer runs, executed
# unfused and chain-fused — bit-exact agreement plus launch accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ChainCase:
    """One forwarding-chain program: expected chain lowering + launch drop."""

    name: str
    build: Callable[[], tuple[TMProgram, dict[str, tuple[int, ...]]]]
    expect_chain_paths: tuple[str, ...]  # chain-record paths at batch_dims=0
    launches_unfused: int
    launches_chained: int
    dtypes: tuple[str, ...] = ALL_DTYPES
    supports_batch: bool = True
    scale: float = 100.0


def _chain3():
    """transpose → split → transpose, no epilogues (pure-map run)."""
    return _chain()


def _chain_superres():
    """pixelshuffle+Add → crop → re-pad: the superres tail with an epilogue
    pinning the first boundary and an OOB fill pinning the last."""
    mps = af.pixel_shuffle_map((6, 10, 8), 2)
    crop = af.pad_map((12, 20, 2), (-1, -1, 0), (-1, -1, 0))
    pad = af.pad_map((10, 18, 2), (1, 1, 0), (1, 1, 0))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x", "skip"), "a", map_=mps, ew=EwOp.ADD),
         TMInstr(TMOpcode.COARSE, ("a",), "b", map_=crop),
         TMInstr(TMOpcode.COARSE, ("b",), "y", map_=pad)],
        inputs=("x", "skip"), outputs=("y",))
    return prog, {"x": (6, 10, 8), "skip": (12, 20, 2)}


def _chain_route():
    """upsample → Route: the chain streams into one band of a multi-band
    terminal while the other band gathers from its own source."""
    mu = af.upsample_map((5, 7, 3), 2)
    maps = tuple(af.route_maps([(10, 14, 3), (10, 14, 5)]))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("u",), "v", map_=mu),
         TMInstr(TMOpcode.COARSE, ("v", "skip"), "y", maps=maps)],
        inputs=("u", "skip"), outputs=("y",))
    return prog, {"u": (5, 7, 3), "skip": (10, 14, 5)}


def _chain_rme():
    """reshape → Bboxcal: the layout step pulled into the RME kernel load."""
    mr = af.reshape_map((3, 90), (3, 15, 6))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("p",), "r", map_=mr),
         TMInstr(TMOpcode.FINE_EVALUATE, ("r",), "y",
                 rme=RMEConfig(scheme="evaluate", threshold=50.0, cmp="ge",
                               score_index=2, capacity=8),
                 meta={"batch_dims": 1})],
        inputs=("p",), outputs=("y",))
    return prog, {"p": (3, 90)}


def _chain_broken():
    """transpose → split → transpose with the first intermediate ALSO read
    by a trailing Add: the multi-consumer buffer breaks the chain mid-way —
    only the (1, 2) suffix fuses and 'a' must still materialize."""
    m1 = af.transpose_map((4, 6, 8))
    m2 = af.split_map((6, 4, 8), 2, 1)
    m3 = af.transpose_map((6, 4, 4))
    ident = af.identity_map((6, 4, 8))
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x",), "a", map_=m1),
         TMInstr(TMOpcode.COARSE, ("a",), "b", map_=m2),
         TMInstr(TMOpcode.COARSE, ("b",), "c", map_=m3),
         TMInstr(TMOpcode.COARSE, ("a", "r"), "y", map_=ident, ew=EwOp.ADD)],
        inputs=("x", "r"), outputs=("y", "c"))
    return prog, {"x": (4, 6, 8), "r": (6, 4, 8)}


CHAIN_CASES = [
    ChainCase("chain3", _chain3, ("pallas.chain",),
              launches_unfused=3, launches_chained=1),
    ChainCase("chain_superres", _chain_superres, ("pallas.chain",),
              launches_unfused=3, launches_chained=1),
    ChainCase("chain_route", _chain_route, ("pallas.chain+route",),
              launches_unfused=3, launches_chained=1),
    ChainCase("chain_rme", _chain_rme, ("pallas.chain+rme.evaluate",),
              launches_unfused=2, launches_chained=1),
    ChainCase("chain_broken", _chain_broken, ("pallas.chain",),
              launches_unfused=4, launches_chained=3),
]

CHAIN_CASES_BY_NAME = {c.name: c for c in CHAIN_CASES}


def run_chain_differential(case: ChainCase, dtype: str, batch_dims: int,
                           rng: np.random.RandomState):
    """Run one chain case unfused and chain-fused on pallas, against the
    reference engine; assert bit-exactness and honest launch accounting.
    Returns the chained lowering report."""
    prog, shapes = case.build()
    op_view = OpCase(case.name, case.build, (), dtypes=case.dtypes,
                     scale=case.scale)
    bufs = make_inputs(op_view, shapes, dtype, batch_dims, rng)
    ref = TMExecutor(backend="reference")
    unfused = TMExecutor(backend="pallas")
    chained = TMExecutor(backend="pallas", fuse_chains=True)
    r_ref, _, _ = ref.run(prog, bufs, batch_dims=batch_dims)
    r_unf, rep_unf, _ = unfused.run(prog, bufs, batch_dims=batch_dims)
    r_chn, rep_chn, _ = chained.run(prog, bufs, batch_dims=batch_dims)
    assert_agree(op_view, r_ref, r_unf, "ref/pallas")
    assert_agree(op_view, r_ref, r_chn, "ref/chained")
    assert rep_unf.launch_count() == case.launches_unfused, (
        case.name, rep_unf.records)
    assert rep_chn.launch_count() == case.launches_chained, (
        case.name, rep_chn.records)
    chain_paths = tuple(r.path for r in rep_chn.records if r.is_chain)
    assert chain_paths == case.expect_chain_paths, (
        case.name, chain_paths, rep_chn.records)
    # instruction accounting must balance: chained records cover them all
    assert rep_chn.instr_count() == rep_unf.instr_count() == len(prog.instrs)
    return rep_chn


# ---------------------------------------------------------------------------
# compiled-program differential cases: whole jax functions through
# repro.compiler.tm_compile, executed on every backend and checked against
# the uncompiled function — same dtype/batch/odd-shape discipline as above.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompiledCase:
    """One compiler demo: builds (fn, example_args) per shape variant."""

    name: str
    build: Callable  # (dtype, variant, rng) -> (fn, args tuple)
    variants: tuple            # shape/batch variants (passed to build)
    dtypes: tuple[str, ...] = ALL_DTYPES
    exact: bool = True
    atol: float = 0.0


def _arr(rng, shape, dtype, scale=100.0):
    if dtype.startswith("int"):
        lo, hi = (-99, 100) if dtype == "int8" else (-100, 100)
        return jnp.asarray(rng.randint(lo, hi, size=shape).astype(dtype))
    return jnp.asarray((rng.rand(*shape) * scale).astype(np.float32)).astype(dtype)


def _superres_case(dtype, variant, rng):
    from repro.models.cnn import superres_tail
    B, H, W, C = variant
    s = 2
    x = _arr(rng, (B, H, W, C), dtype)
    skip = _arr(rng, (B, H * s, W * s, C // (s * s)), dtype)
    return (lambda a, b: superres_tail(a, b, s=s)), (x, skip)


def _espcn_case(dtype, variant, rng):
    import jax
    from repro.models import cnn
    B, H, W = variant
    p = cnn.init_espcn(jax.random.PRNGKey(0), s=2,
                       dtype=jnp.dtype(dtype))
    x = _arr(rng, (B, H, W, 3), dtype, scale=1.0)
    return (lambda a: cnn.espcn(p, a)), (x,)


def _neck_case(dtype, variant, rng):
    from repro.models.cnn import yolo_neck
    B, H, W, C = variant
    u = _arr(rng, (B, H, W, C), dtype)
    skip = _arr(rng, (B, H * 2, W * 2, C // 2), dtype)
    return yolo_neck, (u, skip)


def _detect_case(dtype, variant, rng):
    from repro.models.cnn import detect_tail
    batch, N, D = variant
    pred = _arr(rng, batch + (N, D), dtype)
    return (lambda p: detect_tail(p, 10.0, 16)), (pred,)


COMPILED_CASES = [
    # odd, non-tile-aligned spatial shapes on purpose
    CompiledCase("superres_tail", _superres_case,
                 variants=((1, 6, 10, 8), (3, 5, 7, 8), (2, 4, 4, 16))),
    CompiledCase("espcn", _espcn_case,
                 variants=((1, 10, 14), (2, 7, 9)),
                 dtypes=FLOAT_DTYPES),
    CompiledCase("yolo_neck", _neck_case,
                 variants=((1, 5, 7, 6), (2, 4, 6, 8))),
    CompiledCase("detect_tail", _detect_case,
                 variants=(((2,), 33, 7), ((2, 3), 20, 6))),
]

COMPILED_CASES_BY_NAME = {c.name: c for c in COMPILED_CASES}


def run_compiled_differential(case: CompiledCase, dtype: str, variant,
                              rng: np.random.RandomState):
    """Compile one demo and check every backend against the raw function."""
    from repro.compiler import tm_compile

    fn, args = case.build(dtype, variant, rng)
    ref = fn(*args)
    compiled = tm_compile(fn, *args)
    for backend in BACKENDS:
        got = compiled(*args, backend=backend)
        x = np.asarray(ref, dtype=np.float64)
        y = np.asarray(got, dtype=np.float64)
        assert x.shape == y.shape, (case.name, backend, x.shape, y.shape)
        if case.exact:
            assert np.array_equal(x, y), (case.name, backend, dtype, variant)
        else:
            np.testing.assert_allclose(
                x, y, atol=case.atol, rtol=0,
                err_msg=f"{case.name}:{backend}:{dtype}")
    return compiled


# ---------------------------------------------------------------------------
# cross-engine cases: a compute eqn forwarding into (or fed by) an adjacent
# TM run.  Compiled under ``cross_engine=True`` the crossing must partition
# as ONE fused phase and — on the pallas backend — realize as ONE
# ``pallas.xchain`` launch, bit-exact against the eager function, against
# the non-crossing compilation, and across all three backends (reference /
# fused take the split path inside the fused phase).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class XEngineCase:
    """One engine-boundary crossing program."""

    name: str
    build: Callable  # (dtype, variant, rng) -> (fn, args tuple)
    direction: str                       # expected crossing direction
    variants: tuple                      # shape variants (passed to build)
    tm_links: int = 1                    # TM instrs riding the crossing
    dtypes: tuple[str, ...] = ALL_DTYPES


def _x_mm_transpose(dtype, variant, rng):
    M, K, N = variant
    a = _arr(rng, (M, K), dtype)
    b = _arr(rng, (K, N), dtype)
    return (lambda p, q: (p @ q).T), (a, b)


def _x_mm_pixelshuffle(dtype, variant, rng):
    H, W, C, s, K = variant

    def fn(p, q):
        y = (p @ q).reshape(H, W, C, s, s)
        return jnp.transpose(y, (0, 3, 1, 4, 2)).reshape(H * s, W * s, C)

    a = _arr(rng, (H * W, K), dtype)
    b = _arr(rng, (K, C * s * s), dtype)
    return fn, (a, b)


def _x_mm_pad_chain(dtype, variant, rng):
    M, K, N = variant
    a = _arr(rng, (M, K), dtype)
    b = _arr(rng, (K, N), dtype)
    return (lambda p, q: jnp.pad((p @ q).T, ((1, 1), (2, 2)))), (a, b)


def _x_transpose_mm(dtype, variant, rng):
    M, K, N = variant
    a = _arr(rng, (K, M), dtype)     # transposed layout feeding the matmul
    b = _arr(rng, (K, N), dtype)
    return (lambda p, q: p.T @ q), (a, b)


def _x_pad_mm(dtype, variant, rng):
    M, K, N = variant
    a = _arr(rng, (M, K - 2), dtype)  # pad restores K before the matmul
    b = _arr(rng, (K, N), dtype)
    return (lambda p, q: jnp.pad(p, ((0, 0), (1, 1))) @ q), (a, b)


XENGINE_CASES = [
    # odd, non-tile-aligned shapes on purpose (remainder handling)
    XEngineCase("mm_transpose", _x_mm_transpose, "compute_to_tm",
                variants=((24, 16, 40), (7, 9, 5), (33, 12, 20))),
    XEngineCase("mm_pixelshuffle", _x_mm_pixelshuffle, "compute_to_tm",
                variants=((4, 6, 5, 2, 16), (3, 5, 2, 3, 8))),
    XEngineCase("mm_pad_chain", _x_mm_pad_chain, "compute_to_tm",
                variants=((24, 16, 40), (6, 10, 14)), tm_links=2),
    XEngineCase("transpose_mm", _x_transpose_mm, "tm_to_compute",
                variants=((24, 16, 40), (9, 7, 5))),
    XEngineCase("pad_mm", _x_pad_mm, "tm_to_compute",
                variants=((24, 16, 40), (6, 11, 9))),
]

XENGINE_CASES_BY_NAME = {c.name: c for c in XENGINE_CASES}


def run_xengine_differential(case: XEngineCase, dtype: str, variant,
                             rng: np.random.RandomState):
    """Compile one crossing under ``cross_engine`` on AND off; assert the
    fused partition, the single realized ``pallas.xchain`` launch, and
    bit-exact agreement everywhere.  Returns the fused compilation."""
    from repro.compiler import tm_compile

    fn, args = case.build(dtype, variant, rng)
    ref = np.asarray(fn(*args), dtype=np.float64)
    base = tm_compile(fn, *args)
    fused = tm_compile(fn, *args, cross_engine=True)

    part = fused.partition_report
    assert part.xengine_phases == 1, (case.name, part.summary())
    (fp,) = part.fused_phases
    assert fp.xengine.direction == case.direction, (
        case.name, fp.xengine.direction)
    assert len(fp.xengine.tm_indices) == case.tm_links, (
        case.name, fp.xengine.tm_indices)

    for backend in BACKENDS:
        got, reps = fused.run(*args, backend=backend)
        y = np.asarray(got, dtype=np.float64)
        assert ref.shape == y.shape, (case.name, backend, ref.shape, y.shape)
        assert np.array_equal(ref, y), (case.name, backend, dtype, variant)
        if backend == "pallas":
            recs = [r for rep in reps for r in rep.records]
            xrecs = [r for r in recs if r.path.startswith("pallas.xchain")]
            assert len(xrecs) == 1, (case.name, recs)
            assert xrecs[0].launches == 1
            assert xrecs[0].instrs == case.tm_links + 1  # eqn counted too

    # and the non-crossing compilation is bit-identical on every backend
    for backend in BACKENDS:
        got_base, _ = base.run(*args, backend=backend)
        assert np.array_equal(ref, np.asarray(got_base, dtype=np.float64)), (
            case.name, backend, "base")
    return fused
