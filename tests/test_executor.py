"""8-stage executor + fusion pass: reference == fused, traffic accounting,
and the paper's reconfigurability claim (new op = new registers only)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import affine as af
from repro.core.executor import TMExecutor
from repro.core.fusion import fuse
from repro.core.instr import EwOp, RMEConfig, TMInstr, TMOpcode, TMProgram


def _chain_program():
    m1 = af.transpose_map((4, 6, 8))
    m2 = af.split_map((6, 4, 8), 2, 1)
    m3 = af.transpose_map((6, 4, 4))
    return TMProgram(
        instrs=[
            TMInstr(TMOpcode.COARSE, ("x",), "a", map_=m1),
            TMInstr(TMOpcode.COARSE, ("a",), "b", map_=m2),
            TMInstr(TMOpcode.COARSE, ("b",), "y", map_=m3),
        ],
        inputs=("x",), outputs=("y",),
    )


def test_reference_vs_fused_equal(rng):
    prog = _chain_program()
    x = jnp.asarray(rng.rand(4, 6, 8).astype(np.float32))
    ref = TMExecutor(backend="reference")(prog, {"x": x})["y"]
    ex = TMExecutor(backend="fused")
    got = ex(prog, {"x": x})["y"]
    assert np.array_equal(np.asarray(ref), np.asarray(got))
    assert ex.last_report.fused_pairs == 2
    assert ex.last_report.elided_buffers == ["a", "b"]


def test_fusion_traffic_reduction():
    prog = _chain_program()
    fused, rep = fuse(prog)
    assert len(fused.instrs) == 1
    # 3 load+store pairs collapse to 1: traffic drops by the two
    # intermediates' load+store (near-memory execution, Fig. 10b analogue)
    assert rep.bytes_after < rep.bytes_before
    assert rep.traffic_reduction > 0.4


def test_unfusable_falls_back_to_two_instructions(rng):
    """Maps that don't compose exactly run as two engine passes (same as a
    TMU issuing two instructions) — never silently wrong."""
    m1 = af.pixel_shuffle_map((4, 4, 8), 2)   # has splits
    m2 = af.pixel_unshuffle_map((8, 8, 2), 2)  # has splits
    prog = TMProgram(
        [TMInstr(TMOpcode.COARSE, ("x",), "a", map_=m1),
         TMInstr(TMOpcode.COARSE, ("a",), "y", map_=m2)],
        inputs=("x",), outputs=("y",))
    fused, rep = fuse(prog)
    assert rep.fused_pairs == 0 and len(fused.instrs) == 2
    x = jnp.asarray(rng.rand(4, 4, 8).astype(np.float32))
    got = TMExecutor(backend="fused")(prog, {"x": x})["y"]
    assert np.array_equal(np.asarray(got), np.asarray(x))  # PU∘PS = id


def test_elementwise_and_fine_stages(rng):
    x = jnp.asarray(rng.rand(8, 4).astype(np.float32))
    y = jnp.asarray(rng.rand(8, 4).astype(np.float32))
    prog = TMProgram(
        [TMInstr(TMOpcode.ELEMENTWISE, ("x", "y"), "s", ew=EwOp.ADD),
         TMInstr(TMOpcode.FINE_EVALUATE, ("s",), "out",
                 rme=RMEConfig(scheme="evaluate", threshold=1.0, cmp="ge",
                               score_index=0, capacity=8))],
        inputs=("x", "y"), outputs=("out",))
    out = TMExecutor(backend="reference")(prog, {"x": x, "y": y})["out"]
    s = np.asarray(x) + np.asarray(y)
    want = s[s[:, 0] >= 1.0][:8]
    assert np.allclose(np.asarray(out)[:len(want)], want)


def test_program_serialization_roundtrip():
    prog = _chain_program()
    s = prog.encode()
    back = TMProgram.decode(s)
    assert back.encode() == s
    assert [i.map_ for i in back.instrs] == [i.map_ for i in prog.instrs]


def test_reconfigurability_new_op_without_new_datapath(rng):
    """Rot180 was never implemented anywhere — expressing it as a new (A,B)
    register pair must execute on the unchanged engine (the paper's central
    claim, Section IV)."""
    H, W, C = 4, 6, 3
    rot180 = af.MixedRadixMap(
        out_shape=(H, W, C), in_shape=(H, W, C), splits=(),
        affine=af.AffineMap.make(
            [[-1, 0, 0], [0, -1, 0], [0, 0, 1]], [H - 1, W - 1, 0]))
    x = jnp.asarray(rng.rand(H, W, C).astype(np.float32))
    prog = TMProgram([TMInstr(TMOpcode.COARSE, ("x",), "y", map_=rot180)],
                     inputs=("x",), outputs=("y",))
    got = TMExecutor()(prog, {"x": x})["y"]
    assert np.array_equal(np.asarray(got), np.asarray(x)[::-1, ::-1, :])
    # and the generic Pallas kernel also executes it — as row copies: a
    # reversal inside a (8, 128) tile has no Mosaic lowering, so the
    # flipped sublane axis rules block mode out
    from repro.kernels.tm_affine import plan_of, tm_affine_call
    big = af.MixedRadixMap(
        out_shape=(64, 128, 8), in_shape=(64, 128, 8), splits=(),
        affine=af.AffineMap.make(
            [[-1, 0, 0], [0, -1, 0], [0, 0, 1]], [63, 127, 0]))
    xb = jnp.asarray(rng.rand(64, 128, 8).astype(np.float32))
    got2 = tm_affine_call(xb, big, interpret=True)
    assert np.array_equal(np.asarray(got2), np.asarray(xb)[::-1, ::-1, :])
    assert plan_of(big) is None  # decoded to gather mode
