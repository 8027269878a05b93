"""Row gathers — the TMU address tables in a form Mosaic compiles.

A coarse map is a gather: output element ``o`` reads input element
``idx[o]``.  A flat element gather (``jnp.take`` on a flattened VMEM slab) has
no Mosaic lowering, so every Pallas gather in this package is expressed over
*rows* instead:

* the output is viewed as ``(R, 1, L)`` — ``L`` divides the output's minor
  axis, so the segment plan of :func:`repro.core.schedule.plan_segments`
  (``rows x minor``, blocked by ``row_block``) is still the kernel grid;
* each source is viewed as ``(R_in, 1, L_in)``, and output row ``r`` copies
  the static lane window ``[c, c + w)`` of source row ``rows[r]`` into its
  lanes ``[a, a + w)``; ``rows[r] = -1`` marks a row that reads nothing.

The leading axis of a ``(R, 1, L)`` array is untiled, so a row is addressed
with a dynamic scalar index (an SMEM row table), and the source rows one
output block needs are fetched as one element-offset window
(``pl.Element``) per grid step.  ``L = 1`` always admits a row form, so the
analysis never fails; it only gets slower and hungrier for VMEM as ``L``
shrinks, which the TPU budget check turns into an up-front decline.

A :class:`RowProgram` combines several such gathers with validity masks and
element-wise steps — enough to express a single map, a forwarding chain
(pulled back onto its final output grid), a summed Route and an overlay
(``dynamic_update_slice``) Route as ONE kernel.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.engine import EW_FNS

# a (1, L) row of a (R, 1, L) VMEM array occupies whole (1, 128)-lane tiles;
# for packed dtypes the unit sublane pads to the packing, which keeps a row
# at 512 bytes per 128 lanes whatever the dtype
_LANE = 128
_TILE_ROW_BYTES = 512

# what one launch may keep in VMEM (windows, blocks, scratch, double
# buffering included) and the scoped limit the kernel asks the compiler for
VMEM_BUDGET = 48 << 20
VMEM_LIMIT = 96 << 20
# the per-block window starts are scalar-prefetched into SMEM
_SMEM_TABLE_BUDGET = 256 << 10


def row_bytes(lanes: int) -> int:
    return _TILE_ROW_BYTES * max(1, math.ceil(lanes / _LANE))


# ---------------------------------------------------------------------------
# analysis (numpy, at plan-build time)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class RowGather:
    """One source's gather onto the ``(R, L)`` output row grid."""

    in_rows: int          # R_in: the source viewed as (R_in, 1, l_in)
    l_in: int
    a: int                # out lanes [a, a + w) <- source lanes [c, c + w)
    c: int
    w: int
    rows: np.ndarray      # (R,) int32 source row per output row, -1 = none


def _suffix_products(shape) -> list[int]:
    out, p = [], 1
    for d in reversed(tuple(shape)):
        p *= int(d)
        out.append(p)
    return out


def row_gather(idx: np.ndarray, need: np.ndarray, in_shape, R: int,
               L: int) -> RowGather | None:
    """Row form of the flat gather ``idx`` over the ``(R, L)`` grid, or None.

    ``need`` marks the elements whose value must come from the source.  A
    row whose needed lanes read one contiguous source run starting at a
    fixed lane offset of a source row becomes one row copy; lanes outside
    the (shared, static) window read nothing."""
    n_in = math.prod(in_shape)
    I = idx.reshape(R, L).astype(np.int64)
    N = need.reshape(R, L)
    row_any = N.any(axis=1)
    if not row_any.any():
        return RowGather(in_rows=n_in, l_in=1, a=0, c=0, w=0,
                         rows=np.full(R, -1, np.int32))
    lanes = np.flatnonzero(N.any(axis=0))
    a, w = int(lanes[0]), int(lanes[-1]) + 1 - int(lanes[0])
    first = N.argmax(axis=1)
    base = I[np.arange(R), first] - (first - a)
    expect = base[:, None] + (np.arange(L)[None, :] - a)
    if not np.array_equal(I[N], expect[N]):
        return None
    vb = base[row_any]
    if vb.min() < 0 or vb.max() + w > n_in:
        return None
    for l_in in dict.fromkeys([w] + [p for p in _suffix_products(in_shape)
                                     if p >= w]):
        if n_in % l_in:
            continue
        cs = vb % l_in
        c = int(cs[0])
        if c + w <= l_in and (cs == c).all():
            rows = np.where(row_any, base // l_in, -1).astype(np.int32)
            return RowGather(in_rows=n_in // l_in, l_in=l_in, a=a, c=c, w=w,
                             rows=rows)
    return None


@dataclasses.dataclass(frozen=True, eq=False)
class RowMask:
    """A validity mask on the output row grid: rows ``flags`` on lanes
    ``[a, b)`` (``elems`` is None), or an explicit element mask."""

    a: int = 0
    b: int = 0
    flags: np.ndarray | None = None   # (R,) bool
    elems: np.ndarray | None = None   # (R, L) bool


def row_mask(ok: np.ndarray, R: int, L: int) -> RowMask | None:
    """Compact form of an element mask; None when every element is valid."""
    ok = ok.reshape(R, L)
    if ok.all():
        return None
    lanes = np.flatnonzero(ok.any(axis=0))
    if lanes.size:
        a, b = int(lanes[0]), int(lanes[-1]) + 1
        flags = ok.any(axis=1)
        window = np.zeros(L, bool)
        window[a:b] = True
        if np.array_equal(ok, flags[:, None] & window[None, :]):
            return RowMask(a=a, b=b, flags=flags)
    return RowMask(elems=ok)


def implicit_mask(g: RowGather, R: int, L: int) -> np.ndarray:
    """The validity a gather carries by construction: copied rows on their
    lane window."""
    window = np.zeros(L, bool)
    window[g.a:g.a + g.w] = True
    return (g.rows >= 0)[:, None] & window[None, :]


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class RowProgram:
    """Gathers + masks + a combine recipe over one output row grid.

    ``ops`` is evaluated on whole ``(rb, 1, L)`` blocks after the gathers
    have filled their scratch blocks:

    * ``("load", g)`` — ``v = B_g``
    * ``("mask", m, fill)`` — ``v = where(M_m, v, fill)``
    * ``("ew", name, g)`` — ``v = ew(v, B_g)``
    * ``("add", g)`` — ``v = v + B_g``
    * ``("add_masked", g, m, fill)`` — ``v = v + where(M_m, B_g, fill)``
    * ``("overlay", g, m)`` — ``v = where(M_m, B_g, v)``

    A gather block holds ``fill`` wherever its implicit validity is false.
    """

    out_shape: tuple[int, ...]
    dtype: str
    L: int
    rb: int                               # kernel rows per grid step
    grid: int
    gathers: tuple[RowGather, ...]
    fills: tuple[float, ...]              # per gather
    slab_of: tuple[int, ...]              # runtime operand feeding gather g
    masks: tuple[RowMask, ...]
    ops: tuple[tuple, ...]
    n_slabs: int

    @property
    def rows(self) -> int:
        return self.grid * self.rb


def choose_lanes(minor: int, fits) -> int:
    """Largest divisor ``L`` of the output minor axis for which ``fits(L)``
    holds (``L = 1`` always does for pure row gathers)."""
    for L in sorted((d for d in range(1, minor + 1) if minor % d == 0),
                    reverse=True):
        if fits(L):
            return L
    return 1


def _windows(g: RowGather, grid: int, rb: int):
    """Per-block window starts, block-relative row tables and the window
    height for one gather."""
    rows = g.rows.reshape(grid, rb)
    valid = rows >= 0
    big = np.iinfo(np.int32).max
    lo = np.where(valid, rows, big).min(axis=1)
    hi = np.where(valid, rows, -1).max(axis=1) + 1
    lo = np.where(valid.any(axis=1), lo, 0)
    hi = np.where(valid.any(axis=1), hi, 1)
    W = int(max(1, (hi - lo).max()))
    W = min(W, g.in_rows)
    lo = np.minimum(lo, g.in_rows - W).astype(np.int32)
    rel = np.where(valid, rows - lo[:, None], -1).astype(np.int32)
    return lo, rel.reshape(grid, 1, rb), W


def vmem_bytes(prog: RowProgram) -> int:
    """VMEM one launch holds: double-buffered windows, blocks and masks,
    plus the gather scratch blocks."""
    rbytes = prog.rb * row_bytes(prog.L)
    n = 2 * rbytes                                  # output block
    for g in prog.gathers:
        _, _, W = _windows(g, prog.grid, prog.rb)
        n += 2 * W * row_bytes(g.l_in) + rbytes
    for m in prog.masks:
        n += 2 * rbytes if m.elems is not None else rbytes
    return n


def tpu_decline(prog: RowProgram) -> str | None:
    """Why this program cannot launch on a TPU, or None when it can."""
    need = vmem_bytes(prog)
    if need > VMEM_BUDGET:
        return (f"row gather needs {need} B of VMEM (budget {VMEM_BUDGET}) "
                f"at {prog.L} lanes")
    if 4 * len(prog.gathers) * prog.grid > _SMEM_TABLE_BUDGET:
        return f"{prog.grid}-step grid overflows the SMEM window table"
    return None


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class Head:
    """A compute stage whose result is gather 0's source: ``fn(*operands)``
    runs once, at grid step 0, into a VMEM slab of gather 0's row view (the
    cross-engine commit: the result never goes to HBM)."""

    fn: object                      # (*operand values) -> array
    n_ops: int


@dataclasses.dataclass(frozen=True, eq=False)
class Sink:
    """A compute stage fed by the program's output: every block lands in a
    VMEM slab, and after the last block ``fn(slab value, *operands)``
    produces the kernel's only output (the cross-engine prologue)."""

    fn: object                      # (value of out_shape, *operands) -> array
    n_ops: int
    out_shape: tuple[int, ...]
    out_dtype: object


def _gather_rows(spec: RowGather, t_ref, s_ref, b_ref, rb: int) -> None:
    """Copy the rows of one block: ``b[r, a:a+w] = src[t[r], c:c+w]``."""
    def body(r, carry):
        s = t_ref[0, 0, r]

        @pl.when(s >= 0)
        def _copy():
            row = s_ref[s]
            b_ref[r, :, spec.a:spec.a + spec.w] = \
                row[:, spec.c:spec.c + spec.w]
        return carry

    jax.lax.fori_loop(0, rb, body, 0)


def _combine(prog: RowProgram, blk_refs, mask_refs, mblk_refs):
    """Evaluate ``prog.ops`` on the filled blocks."""
    dtype = jnp.dtype(prog.dtype)

    def mask_value(m):
        if prog.masks[m].elems is not None:
            return mask_refs[m][...] != 0
        k = sum(1 for q in prog.masks[:m] if q.elems is None)
        return mblk_refs[k][...] != 0

    v = None
    for op in prog.ops:
        kind = op[0]
        if kind == "load":
            v = blk_refs[op[1]][...]
        elif kind == "mask":
            v = jnp.where(mask_value(op[1]), v, jnp.asarray(op[2], dtype))
        elif kind == "ew":
            v = EW_FNS[op[1]](v, blk_refs[op[2]][...])
        elif kind == "add":
            v = v + blk_refs[op[1]][...]
        elif kind == "add_masked":
            v = v + jnp.where(mask_value(op[2]), blk_refs[op[1]][...],
                              jnp.asarray(op[3], dtype))
        elif kind == "overlay":
            v = jnp.where(mask_value(op[2]), blk_refs[op[1]][...], v)
        else:
            raise AssertionError(op)
    return v


def _kernel(prog: RowProgram, head: Head | None, sink: Sink | None):
    ng, nm = len(prog.gathers), len(prog.masks)
    n_flag = sum(1 for m in prog.masks if m.elems is None)
    dtype = jnp.dtype(prog.dtype)
    L, rb = prog.L, prog.rb

    def kernel(lo_ref, *refs):
        del lo_ref  # consumed by the window index maps
        step = pl.program_id(0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, L), 2)
        it = iter(refs)
        head_refs = [next(it) for _ in range(head.n_ops)] if head else []
        tables, slabs = [], []
        for g in range(ng):
            tables.append(next(it))
            slabs.append(None if (head and g == 0) else next(it))
        mask_refs = [next(it) for _ in range(nm)]
        sink_refs = [next(it) for _ in range(sink.n_ops)] if sink else []
        o_ref = next(it)
        blk_refs = [next(it) for _ in range(ng)]
        mblk_refs = [next(it) for _ in range(n_flag)]
        stage_ref = next(it) if (head or sink) else None

        if head:
            g0 = prog.gathers[0]

            @pl.when(step == 0)
            def _compute():
                y = head.fn(*[r[...] for r in head_refs])
                stage_ref[:, 0, :] = y.reshape(g0.in_rows, g0.l_in)
            slabs[0] = stage_ref

        for g, spec in enumerate(prog.gathers):
            b_ref = blk_refs[g]
            b_ref[...] = jnp.full(b_ref.shape, prog.fills[g], dtype)
            if spec.w:
                _gather_rows(spec, tables[g], slabs[g], b_ref, rb)

        k = 0
        for m, spec in enumerate(prog.masks):
            if spec.elems is not None:
                continue
            f_ref, mb_ref = mask_refs[m], mblk_refs[k]
            k += 1
            window = ((lane >= spec.a) & (lane < spec.b)).astype(jnp.int32)

            def mbody(r, carry, f_ref=f_ref, mb_ref=mb_ref, window=window):
                mb_ref[pl.ds(r, 1)] = window * f_ref[0, 0, r]
                return carry

            jax.lax.fori_loop(0, rb, mbody, 0)

        v = _combine(prog, blk_refs, mask_refs, mblk_refs)
        if sink is None:
            o_ref[...] = v.astype(o_ref.dtype)
            return
        stage_ref[pl.ds(step * rb, rb)] = v

        @pl.when(step == prog.grid - 1)
        def _compute():
            xv = stage_ref[:, 0, :].reshape(prog.out_shape)
            o_ref[...] = sink.fn(xv, *[r[...] for r in sink_refs]).astype(
                o_ref.dtype)

    return kernel


def _full(shape):
    nd = len(shape)
    return pl.BlockSpec(tuple(shape), lambda i, lo, _nd=nd: (0,) * _nd)


def address_tables(prog: RowProgram, head: bool = False) -> tuple:
    """The program's address tables as device arrays, built once per
    program: the window starts (scalar-prefetched), one row table per
    gather, one array per mask.  They ride into the kernel as runtime
    operands, so no compiled program embeds them as constants."""
    cache = prog.__dict__.setdefault("_tables", {})
    if head not in cache:
        G, rb, L = prog.grid, prog.rb, prog.L
        wins = [_windows(g, G, rb) for g in prog.gathers]
        if head:  # the resident slab: absolute rows, no window
            g0 = prog.gathers[0]
            wins[0] = (np.zeros(G, np.int32),
                       g0.rows.reshape(G, 1, rb).astype(np.int32), g0.in_rows)
        masks = [m.elems.reshape(prog.rows, 1, L) if m.elems is not None
                 else m.flags.reshape(G, 1, rb) for m in prog.masks]
        # concrete even when first asked for under a trace: the cache
        # outlives it
        with jax.ensure_compile_time_eval():
            cache[head] = (
                jnp.asarray(np.concatenate([w[0] for w in wins])
                            .astype(np.int32)),
                tuple(jnp.asarray(w[1]) for w in wins),
                tuple(jnp.asarray(a.astype(np.int32)) for a in masks),
                tuple(w[2] for w in wins))
    return cache[head]


def build_call(prog: RowProgram, interpret: bool, *, head: Head | None = None,
               sink: Sink | None = None, name: str):
    """``call(tables, *head operands, *slabs, *sink operands) -> out`` for
    ``prog`` (jit-able; ``tables`` is :func:`address_tables` of the
    program).  With
    a ``head``, gather 0 reads the head's VMEM result and takes no slab.
    ``name`` is the kernel's family, given by the caller (gather mode,
    chains, overlay routes and the cross-engine kernels all launch here),
    so a device trace tells them apart."""
    G, rb, L = prog.grid, prog.rb, prog.L
    dtype = jnp.dtype(prog.dtype)
    Ws = address_tables(prog, head is not None)[3]
    smem_row = pl.BlockSpec((1, 1, rb), lambda i, lo: (i, 0, 0),
                            memory_space=pltpu.SMEM)
    mask_specs = [pl.BlockSpec((rb, 1, L), lambda i, lo: (i, 0, 0))
                  if m.elems is not None else smem_row for m in prog.masks]
    scratch = [pltpu.VMEM((rb, 1, L), dtype) for _ in prog.gathers]
    scratch += [pltpu.VMEM((rb, 1, L), jnp.int32)
                for m in prog.masks if m.elems is None]
    if head:
        g0 = prog.gathers[0]
        scratch.append(pltpu.VMEM((g0.in_rows, 1, g0.l_in), dtype))
    elif sink:
        scratch.append(pltpu.VMEM((prog.rows, 1, L), dtype))
    kernel = _kernel(prog, head, sink)

    # operands: head operands, the slabs (slab 0 omitted with a head), then
    # sink operands
    n_head = head.n_ops if head else 0
    slab_base = n_head - 1 if head else 0
    n_before = slab_base + prog.n_slabs

    def call(tabs, *operands):
        lo, row_tables, mask_args, _ = tabs
        args, specs = [], []
        for op in operands[:n_head]:
            args.append(op)
            specs.append(_full(op.shape))
        for g, spec in enumerate(prog.gathers):
            args.append(row_tables[g])
            specs.append(smem_row)
            if head and g == 0:
                continue
            x = operands[slab_base + prog.slab_of[g]]
            W = Ws[g]
            args.append(x.reshape(spec.in_rows, 1, spec.l_in))
            specs.append(pl.BlockSpec(
                (pl.Element(W), pl.Element(1), pl.Element(spec.l_in)),
                lambda i, lo, off=g * G: (lo[off + i], 0, 0)))
        args += list(mask_args)
        specs += mask_specs
        if sink:
            for op in operands[n_before:]:
                args.append(op)
                specs.append(_full(op.shape))
            out_shape = jax.ShapeDtypeStruct(sink.out_shape, sink.out_dtype)
            out_spec = _full(sink.out_shape)
        else:
            out_shape = jax.ShapeDtypeStruct((prog.rows, 1, L), dtype)
            out_spec = pl.BlockSpec((rb, 1, L), lambda i, lo: (i, 0, 0))
        out = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(G,), in_specs=specs,
                out_specs=out_spec, scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=VMEM_LIMIT),
            interpret=interpret,
            name=name,
        )(lo, *args)
        return out if sink else out.reshape(prog.out_shape)

    return call


@partial(jax.jit, static_argnums=(0, 1, 2))
def _run(prog: RowProgram, interpret: bool, name: str, tabs, *slabs):
    return build_call(prog, interpret, name=name)(tabs, *slabs)


def run(prog: RowProgram, slabs, *, interpret: bool, name: str):
    """Execute ``prog`` on its runtime operands (one jit per program) as a
    kernel of family ``name``."""
    tabs = address_tables(prog)
    return _run(prog, interpret, name, tabs[:3] + (None,), *slabs)
