"""Generic coarse-grained TM Pallas kernel — the TPU-native address generator.

Two execution modes, selected by analyzing the :class:`MixedRadixMap` (the
"instruction decode" step of the TMU, performed at trace time):

* **block mode** — the map lifts to *block* granularity: every output block
  is exactly one input block (possibly permuted).  Then the Pallas
  ``BlockSpec.index_map`` IS the paper's address generator: the grid
  sequencer evaluates the affine block map each step to drive the HBM→VMEM
  DMA, and the kernel body applies only the intra-block axis permutation.
  Reversed axes are addressed at block granularity (block size 1 on an
  untiled axis), since a reversal inside a tile has no Mosaic lowering.
  Covers Transpose, Split/Route bands, Add, head-layout permutes — zero
  index tensors, pure DMA re-addressing.

* **gather mode** — general fallback: the map runs as the one-link chain of
  :mod:`repro.kernels.tm_affine.chain`, whose address tables are row
  gathers (:mod:`repro.kernels.tm_affine.rows`) computed at trace time,
  exactly like loading the TMU's address registers.  Covers PixelShuffle,
  Img2col, Rearrange, Upsample and any future (A, B) pair.

Block shapes obey the TPU tiling rule on both sides of the DMA: the last two
dimensions of every block are multiples of (8, 128) or span the whole axis.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.affine import MixedRadixMap
from repro.core.engine import EW_FNS
from repro.core.schedule import CycleParams
from repro.platform import pallas_interpret


# ---------------------------------------------------------------------------
# block-mode analysis
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """Lifted block-level form of a signed-permutation affine map.

    For out axis ``i``: input axis ``src_axis[i]`` supplies the data;
    ``sign[i]`` = ±1 (−1 ⇒ reversed); ``offset[i]`` = constant shift in
    elements.  Validity: in_coord[src_axis[i]] = sign[i]·out_coord[i] +
    offset[i], offsets divisible by the chosen block size.
    """

    src_axis: tuple[int, ...]
    sign: tuple[int, ...]
    offset: tuple[int, ...]
    block: tuple[int, ...]          # out-block shape
    grid: tuple[int, ...]           # out grid
    perm: tuple[int, ...]           # in-block axis permutation for the body


@lru_cache(maxsize=1024)
def analyze_block_mode(m: MixedRadixMap,
                       block: tuple[int, ...] | None = None,
                       segment_bytes: int | None = None) -> BlockPlan | None:
    """Return a BlockPlan if the map is a signed permutation w/ liftable offsets.

    ``segment_bytes`` bounds the block (one ping-pong buffer) — the same
    constant the cycle model segments with (:class:`CycleParams`), so the
    kernel grid and the schedule's block-iteration count agree."""
    if m.splits or m.digit_bounds or m.oob_possible:
        return None  # block mode has no validity mask: OOB fill needs gather
    n_out, n_in = len(m.out_shape), len(m.in_shape)
    if n_out != n_in:
        return None
    src_of_in: dict[int, tuple[int, int, int]] = {}  # in_axis -> (out_axis, sign, off)
    for i, (row, off) in enumerate(zip(m.affine.A, m.affine.b)):
        nz = [(j, a) for j, a in enumerate(row) if a != 0]
        if len(nz) != 1:
            return None
        j, a = nz[0]
        if a not in (1, -1) or off.denominator != 1:
            return None
        src_of_in[i] = (j, int(a), int(off))
    if len(src_of_in) != n_in:
        return None
    # invert: for each out axis, which in axis it feeds
    src_axis = [0] * n_out
    sign = [1] * n_out
    offset = [0] * n_out
    for in_ax, (out_ax, s, off) in src_of_in.items():
        src_axis[out_ax] = in_ax
        sign[out_ax] = s
        offset[out_ax] = off
    if block is None:
        block = _legal_block(m, src_axis, sign, offset,
                             _default_block(m.out_shape, segment_bytes))
        if block is None:
            return None
    grid = []
    for d, (size, bs) in enumerate(zip(m.out_shape, block)):
        if size % bs:
            return None
        # offsets must be block-aligned on the *input* axis; block size on the
        # input axis equals bs (same axis pairing).  sign=+1: in = out + off,
        # alignment needs off % bs == 0.  sign=-1: in = off - out, the block
        # image is [off-(g+1)bs+1, off-g·bs] — one block iff (off+1) % bs == 0.
        if sign[d] > 0 and offset[d] % bs:
            return None
        if sign[d] < 0 and (bs != 1 or (offset[d] + 1) % bs):
            return None  # no in-tile reversal: flipped axes block at 1
        if m.in_shape[src_axis[d]] % bs:
            return None
        grid.append(size // bs)
    # perm for the body: out-block axes gather from in-block axes src_axis
    return BlockPlan(tuple(src_axis), tuple(sign), tuple(offset),
                     tuple(block), tuple(grid), tuple(src_axis))


def _granule(pos: int, ndim: int) -> int:
    """Block-size granule the TPU tiling imposes on axis ``pos`` of an
    ``ndim``-d array: 128 lanes, 8 sublanes, anything on leading axes."""
    if pos == ndim - 1:
        return 128
    if pos == ndim - 2:
        return 8
    return 1


def _legal_block(m: MixedRadixMap, src_axis, sign, offset,
                 block: tuple[int, ...]) -> tuple[int, ...] | None:
    """Round ``block`` up so the out block and its permuted in block both
    satisfy the tiling rule (multiple of the granule, or the whole axis);
    flipped axes must block at 1.  None when no legal block exists."""
    n = len(block)
    blk = list(block)
    for d in range(n):
        size, in_size = m.out_shape[d], m.in_shape[src_axis[d]]
        g = math.lcm(_granule(d, n), _granule(src_axis[d], n))
        if sign[d] < 0:
            if g != 1:
                return None
            blk[d] = 1
            continue
        if size % g == 0 and blk[d] % g:
            blk[d] = next(b for b in range(g, size + 1, g) if size % b == 0
                          and b >= blk[d])
        elif size % g:
            if size != in_size or offset[d] != 0:
                return None  # only a whole axis is legal, and it is not one
            blk[d] = size
    in_blk = [0] * n
    for d in range(n):
        in_blk[src_axis[d]] = blk[d]
    out_b, in_b = _vmem_bytes(blk), _vmem_bytes(in_blk)
    # double-buffered in + out blocks, plus the permuted value in flight
    if 2 * (out_b + in_b) + max(out_b, in_b) > _BLOCK_VMEM_CAP:
        return None
    return tuple(blk)


# what a block kernel may hold in VMEM (the compiler's default scoped limit
# is 16 MiB on v5e); blocks that do not fit go to gather mode
_BLOCK_VMEM_CAP = 12 << 20


def _vmem_bytes(block) -> int:
    """A block's VMEM footprint: its last two axes pad to whole (8, 128)
    tiles of 4-byte words (a narrow minor axis costs the full 128 lanes)."""
    b = list(block) or [1]
    lanes = -(-b[-1] // 128) * 128
    rows = -(-b[-2] // 8) * 8 if len(b) > 1 else 1
    return math.prod(b[:-2]) * rows * lanes * 4


def _default_block(shape: tuple[int, ...],
                   segment_bytes: int | None = None) -> tuple[int, ...]:
    """(…, 8·k, 128·m)-aligned (or whole-axis) blocks sized to one
    ping-pong segment.

    The budget is ``CycleParams.segment_bytes`` — the block IS the schedule
    pass's block iteration, so grid size == the cycle model's segment count.
    Minor/sublane dims first, then leading dims grow greedily (largest
    divisor that still fits), so small tensors collapse to a single block."""
    budget = segment_bytes if segment_bytes is not None \
        else CycleParams().segment_bytes
    itemsize = 4
    blk = list(shape)
    if len(shape) >= 1:
        blk[-1] = 128 if shape[-1] % 128 == 0 else shape[-1]
    if len(shape) >= 2:
        blk[-2] = math.gcd(shape[-2], 256) if shape[-2] % 8 == 0 \
            else shape[-2]
        # a multiple of 8 dividing the axis: halving keeps both properties
        while math.prod(blk[-2:]) * itemsize > budget and blk[-2] > 8 \
                and blk[-2] % 16 == 0:
            blk[-2] //= 2
    for d in range(len(shape) - 3, -1, -1):
        blk[d] = 1
    for d in range(len(shape) - 3, -1, -1):
        cap = budget // max(1, math.prod(blk) * itemsize // max(1, blk[d]))
        blk[d] = _largest_divisor_at_most(shape[d], cap)
    return tuple(blk)


def _largest_divisor_at_most(n: int, cap: int) -> int:
    if cap >= n:
        return n
    best, i = 1, 1
    while i * i <= n:
        if n % i == 0:
            for k in (i, n // i):
                if best < k <= cap:
                    best = k
        i += 1
    return best


# ---------------------------------------------------------------------------
# block-mode kernel
# ---------------------------------------------------------------------------

def _block_kernel(plan: BlockPlan, ew=None):
    def kernel(x_ref, *rest):
        o_ref = rest[-1]
        val = x_ref[...]
        # un-permute: out-block axis i <- in-block axis plan.perm[i]
        val = jnp.transpose(val, axes=plan.perm) if plan.perm != tuple(
            range(len(plan.perm))) else val
        if ew is not None:  # fused element-wise epilogue (same pipeline pass)
            val = ew(val, rest[0][...])
        o_ref[...] = val
    return kernel


@lru_cache(maxsize=256)
def _block_launcher(plan: BlockPlan, in_shape: tuple[int, ...],
                    out_shape: tuple[int, ...], dtype, ew: str | None,
                    interpret: bool):
    """The jitted block-mode launch of one static signature: built, traced
    and compiled on its first call, then reused.

    ``pl.pallas_call`` returns a fresh jitted function each time it is
    built, so building it per call recompiles the kernel on every eager
    launch.  The key is what the compiled kernel depends on: the plan, the
    map's shapes, the dtype and the epilogue by its
    :data:`~repro.core.engine.EW_FNS` name.  ``in_shape`` is only a key
    (the launch's jit would compile again for another input shape), so a
    cache miss is a compile; :func:`block_launch_cache_info` counts them.
    Under an outer jit the launch inlines."""
    n = len(plan.grid)

    def in_index(*gidx):
        # address generation at block granularity: the paper's Eq. 1 with
        # coordinates in units of blocks.
        out = [0] * n
        for d in range(n):
            g = gidx[d]
            bs = plan.block[d]
            if plan.sign[d] > 0:
                ib = g + plan.offset[d] // bs          # in = out + off
            else:
                ib = (plan.offset[d] + 1) // bs - 1 - g  # in = off - out
            out[plan.src_axis[d]] = ib
        return tuple(out)

    in_block = [0] * n
    for d in range(n):
        in_block[plan.src_axis[d]] = plan.block[d]

    in_specs = [pl.BlockSpec(tuple(in_block), in_index)]
    if ew is not None:  # epilogue operand streams in output layout
        in_specs.append(pl.BlockSpec(plan.block, lambda *g: g))
    return pl.pallas_call(
        _block_kernel(plan, EW_FNS[ew] if ew is not None else None),
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(plan.block, lambda *g: g),
        out_shape=jax.ShapeDtypeStruct(out_shape, dtype),
        name="tm_affine_block",
        interpret=interpret,
    )


def block_launch_cache_info():
    """Hits and misses of the block-mode launch cache (a
    ``functools`` cache-info tuple): a miss builds and compiles a launch,
    a hit reuses one."""
    return _block_launcher.cache_info()


# ---------------------------------------------------------------------------
# gather mode: the one-link chain
# ---------------------------------------------------------------------------

def gather_sig(m: MixedRadixMap, dtype, ew: str | None = None,
               segment_bytes: int | None = None):
    from repro.kernels.tm_affine.chain import ChainSig
    return ChainSig(links=((m, ew),), dtype=str(jnp.dtype(dtype)),
                    segment_bytes=segment_bytes)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def tm_affine(x: jnp.ndarray, m: MixedRadixMap, *,
              interpret: bool | None = None,
              block: tuple[int, ...] | None = None,
              force_mode: str | None = None,
              y: jnp.ndarray | None = None, ew: str | None = None,
              segment_bytes: int | None = None) -> jnp.ndarray:
    """Execute a MixedRadixMap as a Pallas kernel (decode -> block|gather).

    ``y``/``ew``: optional fused element-wise epilogue — ``ew(map(x), y)``
    (``ew`` an :data:`~repro.core.engine.EW_FNS` name) computed inside the
    kernel while the output block is VMEM-resident (``y`` must have
    ``m.out_shape``).

    ``segment_bytes``: custom ping-pong budget — resizes the block/gather
    grids exactly like :class:`~repro.core.schedule.CycleParams` resizes the
    cycle model's segments (None = the shared default).  ``interpret``
    None: decided by :func:`repro.platform.pallas_interpret`.
    """
    from repro.kernels.tm_affine.chain import tm_chain
    assert x.shape == m.in_shape, (x.shape, m.in_shape)
    assert (y is None) == (ew is None)
    if y is not None:
        assert y.shape == m.out_shape, (y.shape, m.out_shape)
    if interpret is None:
        interpret = pallas_interpret(x)
    plan = (None if force_mode == "gather"
            else analyze_block_mode(m, block, segment_bytes))
    if plan is not None:
        launch = _block_launcher(plan, m.in_shape, m.out_shape,
                                 jnp.dtype(x.dtype), ew, interpret)
        return launch(x) if y is None else launch(x, y)
    sig = gather_sig(m, x.dtype, ew, segment_bytes)
    return tm_chain(sig, x, () if y is None else (y,), interpret=interpret,
                    name="tm_affine_rows")
