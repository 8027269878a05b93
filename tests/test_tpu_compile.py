"""Compile the served path's Pallas kernels for a described TPU v5e chip.

Each test lowers a kernel that the chip smoke's decode or cnn phase runs, at
that phase's real shape, with interpret mode off, and compiles it for one
chip of a ``v5e:2x2`` topology that is described, not attached.  What the
chip's compiler would refuse, these tests refuse — without a chip.  The
topology is described inside a module fixture (never at import), so every
test worker collects the same tests and only the one given this file loads
the TPU library.  Every kernel carries its family's name (``pallas_call``'s
``name=``), which the compiled HLO gives the kernel's instruction, so a
device trace groups kernels by family.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import affine as af
from repro.core.affine import batch_extend_map

B = 4  # requests per served batch in both smoke phases


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    prev = jax.config.jax_enable_compilation_cache
    # a compile for a described chip could be written to a persistent cache
    # but never read back without one: keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(sharding, fn, *shapes, names):
    """Compile ``fn`` for the described chip; the HLO must hold a Mosaic
    kernel (an interpret-mode lowering would hold none), and each of
    ``names`` — the kernels' families — must name a kernel in the lowered
    text and a custom-call instruction in the compiled HLO."""
    sds = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    lowered = jax.jit(fn).lower(*sds)
    low_text = lowered.as_text()
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    for name in names:
        assert f'kernel_name = "{name}"' in low_text, name
        assert f"%{name}." in text, name
    return text


def _family(m, segment_bytes=None) -> str:
    """The kernel family ``tm_affine`` launches for ``m``."""
    from repro.kernels.tm_affine.tm_affine import analyze_block_mode
    return ("tm_affine_block"
            if analyze_block_mode(m, None, segment_bytes) is not None
            else "tm_affine_rows")


def _tm_affine(m, segment_bytes=None):
    from repro.kernels.tm_affine.tm_affine import tm_affine
    return lambda x: tm_affine(x, m, interpret=False,
                               segment_bytes=segment_bytes)


# --- tm_affine block mode: pure DMA re-addressing ---------------------------

@pytest.mark.parametrize("m,dtype", [
    # a transpose of the 200064x3072 phi4-mini embedding table
    (af.axis_permutation_map((200064, 3072), (1, 0)), jnp.bfloat16),
    # decode: GQA scores (.., S, S, group) -> (.., group, S, S)
    (af.axis_permutation_map((1, B, 8, 128, 128, 3), (0, 1, 2, 5, 3, 4)),
     jnp.float32),
], ids=["unembed_transpose", "gqa_scores"])
def test_tm_affine_block_mode_compiles(one_chip, m, dtype):
    """At every segment budget admission may pin: a block that outgrows
    VMEM (a narrow minor axis pads to 128 lanes) must go to gather mode,
    never to a launch the compiler refuses."""
    from repro.kernels.tm_affine import chain as ch
    from repro.kernels.tm_affine.tm_affine import (analyze_block_mode,
                                                   gather_sig)
    from repro.serving.server import DEFAULT_SEGMENT_CANDIDATES
    assert analyze_block_mode(m) is not None
    for sb in DEFAULT_SEGMENT_CANDIDATES:
        if analyze_block_mode(m, None, sb) is None and \
                ch.tpu_decline(gather_sig(m, dtype, None, sb)) is not None:
            continue  # declined up front: the engine takes it
        _compile(one_chip, _tm_affine(m, sb), (m.in_shape, dtype),
                 names=[_family(m, sb)])


# --- tm_affine gather mode: row gathers ------------------------------------

def _qkv_heads():
    """decode: the fused q|k|v projection sliced and split into q heads."""
    sl = af.strided_slice_map((1, B, 128, 5120), (0, 0, 0, 0), (1, 1, 1, 1),
                              (1, B, 128, 3072))
    m = af.compose_maps(af.reshape_map((1, B, 128, 3072),
                                       (1, B, 128, 24, 128)), sl)
    assert m is not None
    return m


@pytest.mark.parametrize("m,dtype", [
    (_qkv_heads(), jnp.bfloat16),
    # cnn: the vmapped conv's batch re-layout at the first backbone stage
    (af.reshape_map((B, 1, 448, 448, 16), (B, 448, 448, 16)), jnp.float32),
    # cnn: the detect tail's head grid laid out as Bboxcal records
    (af.reshape_map((B, 1, 28, 28, 255), (B, 1, 2352, 85)), jnp.float32),
    # a lane-offset channel slice: no tile-aligned DMA can take it
    (af.strided_slice_map((B, 28, 28, 255), (0, 0, 0, 85), (1, 1, 1, 1),
                          (B, 28, 28, 85)), jnp.float32),
], ids=["qkv_heads", "conv_batch_relayout", "detect_records",
        "channel_slice"])
def test_tm_affine_gather_mode_compiles(one_chip, m, dtype):
    from repro.kernels.tm_affine import chain as ch
    from repro.kernels.tm_affine.tm_affine import (analyze_block_mode,
                                                   gather_sig)
    assert analyze_block_mode(m) is None
    assert ch.tpu_decline(gather_sig(m, dtype)) is None
    _compile(one_chip, _tm_affine(m), (m.in_shape, dtype),
             names=["tm_affine_rows"])


def test_upsample_compiles(one_chip):
    m = batch_extend_map(af.upsample_map((14, 14, 128), 2), (B, 1))
    _compile(one_chip, _tm_affine(m), (m.in_shape, jnp.float32),
             names=[_family(m)])


def test_rearrange_compiles(one_chip):
    m = batch_extend_map(af.rearrange_map((448, 448, 3), 1, 16), (B, 1))
    _compile(one_chip, _tm_affine(m), (m.in_shape, jnp.float32),
             names=[_family(m)])


def test_route_bands_compile(one_chip):
    """cnn: the neck's Route — one launch per band."""
    maps = [batch_extend_map(m, (B, 1))
            for m in af.route_maps([(28, 28, 128), (28, 28, 128)])]
    from repro.kernels.tm_affine.tm_affine import tm_affine

    def route(u, skip):
        return (tm_affine(u, maps[0], interpret=False)
                + tm_affine(skip, maps[1], interpret=False))
    shape = (B, 1, 28, 28, 128)
    _compile(one_chip, route, (shape, jnp.float32), (shape, jnp.float32),
             names=sorted({_family(m) for m in maps}))


def test_kv_append_overlay_compiles(one_chip):
    """decode: the KV-cache append as one overlay launch, through the
    overlay rule's own run."""
    from repro.core.instr import TMInstr, TMOpcode
    from repro.kernels.tm_affine.ops import _overlay_run
    cache, upd = (B, 256, 8, 128), (B, 1, 8, 128)
    maps = tuple(af.update_slice_maps(cache, upd, (0, 128, 0, 0)))
    ins = TMInstr(TMOpcode.COARSE, ("c", "u"), "out", maps=maps,
                  meta={"overlay": True})
    _compile(one_chip, lambda c, u: _overlay_run(ins, [c, u], 0, False),
             (cache, jnp.bfloat16), (upd, jnp.bfloat16),
             names=["tm_overlay"])


# --- chains: one launch for a forwarding chain ------------------------------

def _chain_of(fn, *shapes):
    """The first forwarding chain of ``fn``'s TM phases at these shapes,
    as ``(ChainSig, source shape, slab shapes)`` — traced abstractly."""
    from repro.compiler.partition import partition
    from repro.compiler.passes import run_pipeline
    from repro.compiler.trace import graph_from_jaxpr
    from repro.core.fusion import forwarding_chains
    from repro.core.tm_primitive import tag_tm_ops
    from repro.kernels.tm_affine.ops import _chain_sig_build
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    with tag_tm_ops():
        graph = graph_from_jaxpr(jax.make_jaxpr(fn)(*args))
    run_pipeline(graph)
    for ph in partition(graph, None).tmu_phases:
        prog = ph.program
        for c in forwarding_chains(prog):
            instrs = [prog.instrs[k] for k in c.instrs]
            streamed = set(c.buffers[:len(instrs) - 1])
            srcs = [[None if s in streamed else jax.ShapeDtypeStruct(
                graph.shape(s), graph.buffers[s].dtype) for s in ins.srcs]
                for ins in instrs]
            sig, slabs = _chain_sig_build(instrs, srcs, 0, None)
            if sig is not None:
                return sig, srcs[0][0].shape, [s.shape for s in slabs]
    raise AssertionError("no chain the chain rule takes")


@pytest.mark.parametrize("name", ["superres_tail", "yolo_neck"])
def test_chain_compiles(one_chip, name):
    from repro.kernels.tm_affine import chain as ch
    from repro.models import cnn
    if name == "superres_tail":
        sig, x, slabs = _chain_of(lambda a, b: cnn.superres_tail(a, b, s=2),
                                  (B, 56, 56, 256), (B, 112, 112, 64))
    else:
        sig, x, slabs = _chain_of(cnn.yolo_neck, (B, 14, 14, 128),
                                  (B, 28, 28, 128))
    assert ch.tpu_decline(sig) is None
    _compile(one_chip,
             lambda x, *s: ch.tm_chain(sig, x, s, interpret=False),
             (x, jnp.float32), *[(s, jnp.float32) for s in slabs],
             names=["tm_chain"])


# --- RME evaluate: Bboxcal over the detect tail's records --------------------

def test_rme_evaluate_compiles(one_chip):
    from repro.kernels.rme_gather.rme_gather import evaluate_batched
    _compile(one_chip,
             lambda x: evaluate_batched(x, 0.5, 64, score_index=4,
                                        interpret=False),
             ((B, 2352, 85), jnp.float32), names=["rme_gather"])


# --- cross-engine: a dot streamed through a TM chain -------------------------

def _dot_xchain(direction):
    from repro.core.instr import TMInstr, TMOpcode
    from repro.kernels.matmul_tm.ops import _dot_node
    from repro.kernels.tm_affine.ops import _chain_sig_build
    if direction == "compute_to_tm":   # (M, N) result split into heads
        node = _dot_node(1024, 3072, 1024, "bfloat16")
        m = af.reshape_map((1024, 1024), (1024, 8, 128))
        stand_in = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16)
    else:                              # heads merged into the dot's lhs
        node = _dot_node(1024, 1024, 3072, "bfloat16")
        m = af.reshape_map((1024, 8, 128), (1024, 1024))
        stand_in = jax.ShapeDtypeStruct((1024, 8, 128), jnp.bfloat16)
    ins = TMInstr(opcode=TMOpcode.COARSE, srcs=("y",), dst="z", map_=m)
    sig, slabs = _chain_sig_build([ins], [[stand_in]], 0, None)
    return node.eqn, sig


@pytest.mark.parametrize("direction", ["compute_to_tm", "tm_to_compute"])
def test_matmul_xchain_compiles(one_chip, direction):
    from repro.kernels.matmul_tm import chain as xc
    from repro.kernels.tm_affine.chain import build_chain_plan
    eqn, sig = _dot_xchain(direction)
    prog = build_chain_plan(sig).program
    if direction == "compute_to_tm":
        op_sds = (((1024, 3072), "bfloat16"), ((3072, 1024), "bfloat16"))
        assert xc._tpu_decline(direction, eqn, op_sds, prog,
                               (1024, 1024)) is None
        fn, _, _ = xc._commit_executable(sig, eqn, op_sds, False)
        shapes = [(s, jnp.bfloat16) for s, _ in op_sds]
    else:
        op_sds = (((1024, 1024), "bfloat16"), ((1024, 3072), "bfloat16"))
        assert xc._tpu_decline(direction, eqn, op_sds, prog,
                               (1024, 1024)) is None
        fn, _, _ = xc._prologue_executable(sig, eqn, op_sds, 0, False)
        shapes = [((1024, 8, 128), jnp.bfloat16),
                  ((1024, 3072), jnp.bfloat16)]
    _compile(one_chip, fn, *shapes, names=["xchain"])


# --- kernels no served rule launches still compile under their names ---------

def test_matmul_tm_compiles(one_chip):
    """The plain tiled matmul at phi4-mini's q|k|v projection widths."""
    from repro.kernels.matmul_tm.matmul_tm import matmul_tm
    _compile(one_chip, lambda x, w: matmul_tm(x, w, interpret=False),
             ((B * 128, 3072), jnp.bfloat16), ((3072, 5120), jnp.bfloat16),
             names=["matmul_tm"])


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_flash_attention_compiles(one_chip, step):
    """Flash attention over phi4-mini's 24 heads of 128."""
    from repro.kernels.flash_attention.flash_attention import (
        flash_attention, flash_decode)
    kv = ((B * 24, 256, 128), jnp.bfloat16)
    if step == "prefill":
        _compile(one_chip,
                 lambda q, k, v: flash_attention(q, k, v, interpret=False),
                 kv, kv, kv, names=["flash_attention"])
    else:
        _compile(one_chip,
                 lambda q, k, v: flash_decode(q, k, v, 200, interpret=False),
                 ((B * 24, 1, 128), jnp.bfloat16), kv, kv,
                 names=["flash_attention"])


# --- kernels that cannot compile decline up front on a TPU ------------------

def test_resize_declines_on_tpu_before_launch(monkeypatch):
    """Rank-1 tap-table blocks break the TPU block rule: on a TPU the resize
    rule declines, and the engine fallback record carries the reason."""
    import numpy as np
    from repro.core.dispatch import Decline, lower_instr
    from repro.core.instr import TMInstr, TMOpcode
    from repro.kernels.resize import ops as resize_ops
    ins = TMInstr(TMOpcode.RESIZE, ("x",), "y",
                  meta={"out_h": 28, "out_w": 28})
    x = jnp.asarray(np.zeros((14, 14, 128), np.float32))
    monkeypatch.setattr(resize_ops, "pallas_interpret", lambda *a: False)
    why = resize_ops._resize_matches(ins, [x], 0)
    assert isinstance(why, Decline) and "rank-1" in why
    declines: list = []
    assert lower_instr(ins, [x], 0, declines=declines) is None
    assert declines == [("resize", str(why))]
