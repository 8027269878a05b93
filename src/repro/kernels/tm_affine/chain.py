"""Chain megakernel — a producer→consumer run of coarse TM instructions
lowered as ONE segment-streaming Pallas kernel.

Per-instruction lowering executes a forwarding chain as N kernels with N−1
full intermediates round-tripped through HBM.  This kernel collapses the
chain: its grid iterates the *final* output's block iterations
(:func:`repro.core.schedule.plan_segments` — the same segmentation the cycle
model charges), and each grid step assembles one segment of the final output
directly from the chain's sources:

* adjacent links whose maps compose symbolically are pre-coalesced with
  :func:`repro.core.affine.compose_maps` (the fusion pass's composition,
  reused — those intermediates vanish entirely);
* links that do NOT compose (splits/rational interactions, OOB fills,
  element-wise epilogues pinning a boundary) are *pulled back*: at build
  time each link's gather is composed **numerically** onto the final output
  grid, and each resulting address table becomes a row gather
  (:mod:`repro.kernels.tm_affine.rows`).  Per segment the kernel gathers the
  chain source, applies each link's validity mask and epilogue in order, and
  commits the result — the intermediates never exist at tensor granularity.

A terminal multi-band Route (``TMInstr.maps``) is supported as the last
link: the chain streams into its band while the remaining bands gather
directly from their own sources, summed per segment — or, for an overlay
Route (``dynamic_update_slice``), written over the chain band wherever their
map is in bounds.  A single coarse map is the one-link chain, which is how
the tm_affine gather mode runs.
"""

from __future__ import annotations

import dataclasses
import math
from functools import lru_cache

import numpy as np
import jax.numpy as jnp

from repro.core.affine import MixedRadixMap, compose_maps, memoized_hash
from repro.core.engine import gather_indices
from repro.core.schedule import plan_segments
from repro.kernels.tm_affine import rows as rw


@dataclasses.dataclass(frozen=True)
class ChainSig:
    """Hashable chain signature — the cache key for built chain executables.

    ``links`` are the batch-lifted ``(map, ew)`` pairs in dataflow order
    (before composition coalescing); ``route_maps``/``route_band`` describe
    an optional terminal multi-band Route, with the chain feeding band
    ``route_band`` (with no links, band ``route_band`` reads the chain source
    itself).  ``overlay`` gives the Route last-writer-wins semantics instead
    of a band sum.
    """

    links: tuple[tuple[MixedRadixMap, str | None], ...]
    route_maps: tuple[MixedRadixMap, ...] | None = None
    route_band: int = 0
    dtype: str = "float32"
    segment_bytes: int | None = None
    overlay: bool = False

    def __hash__(self):
        # hashed on every executor call (executable-cache lookup) — memoize
        return memoized_hash(self, self.links, self.route_maps,
                             self.route_band, self.dtype, self.segment_bytes,
                             self.overlay)

    @property
    def out_shape(self) -> tuple[int, ...]:
        if self.route_maps is not None:
            return self.route_maps[0].out_shape
        return self.links[-1][0].out_shape


@lru_cache(maxsize=256)
def _coalesce(links: tuple[tuple[MixedRadixMap, str | None], ...],
              ) -> tuple[tuple[MixedRadixMap, str | None], ...]:
    """Symbolically compose adjacent links (the fusion pass's rule: a link
    carrying an epilogue pins its boundary — the operand is consumed in that
    link's output layout)."""
    ls = list(links)
    changed = True
    while changed:
        changed = False
        for i in range(len(ls) - 1):
            (m1, ew1), (m2, ew2) = ls[i], ls[i + 1]
            if ew1 is not None:
                continue
            m = compose_maps(m2, m1)
            if m is None:
                continue
            ls[i:i + 2] = [(m, ew2)]
            changed = True
            break
    return tuple(ls)


def _np_gather(m: MixedRadixMap) -> tuple[np.ndarray, np.ndarray]:
    flat, valid = gather_indices(m, xp=np)   # host-side, even inside a trace
    return (np.broadcast_to(flat, m.out_shape).astype(np.int32).ravel(),
            np.broadcast_to(valid, m.out_shape).ravel())


def fold_pullback(maps: tuple[MixedRadixMap, ...],
                  ) -> tuple[np.ndarray, np.ndarray | None, float]:
    """Numerically compose a run of *pure* maps (no epilogues) onto the last
    map's output grid.

    Returns ``(J, OK, fill)``: flat indices into the first map's input, a
    validity mask (None when no element can go out of bounds) and the fill
    the invalid elements take.  An element invalid at several levels takes
    the LAST level's fill (forward-execution semantics); chains whose
    OOB-capable levels disagree on the fill value raise ``ValueError`` —
    callers decline and fall back to per-instruction lowering.
    """
    out_shape = maps[-1].out_shape
    rm = math.prod(out_shape)
    cur = np.arange(rm, dtype=np.int32)
    decided = np.zeros(rm, dtype=bool)
    fill: float | None = None
    for m in reversed(maps):
        flat, valid = _np_gather(m)
        ib = valid[cur]
        newly = (~ib) & (~decided)
        if newly.any():
            if fill is None:
                fill = float(m.fill)
            elif fill != float(m.fill):
                raise ValueError("mixed fill values across chain levels")
            decided |= newly
        cur = flat[cur]
    ok = None if not decided.any() else ~decided
    return cur, ok, (0.0 if fill is None else fill)


@dataclasses.dataclass(frozen=True, eq=False)
class ChainPlan:
    """The built row program for one chain signature."""

    sig: ChainSig
    program: rw.RowProgram
    n_composed: int               # links eliminated by compose_maps

    @property
    def n_segments(self) -> int:
        return self.program.grid


@dataclasses.dataclass(frozen=True)
class _Pulled:
    """The chain pulled back onto the final output grid (flat, numpy)."""

    j: np.ndarray                               # into the chain source
    in_shape: tuple[int, ...]                   # of the chain source
    levels: tuple[tuple, ...]                   # (ok | None, fill, ew, p, y shape)
    extras: tuple[tuple, ...]                   # (idx, ok, fill, z shape)


def _pull_back(sig: ChainSig, links) -> _Pulled:
    rm = math.prod(sig.out_shape)
    maps_seq = [m for m, _ in links]
    ews = [ew for _, ew in links]
    if sig.route_maps is not None:
        maps_seq.append(sig.route_maps[sig.route_band])
        ews.append(None)
    cur = np.arange(rm, dtype=np.int32)
    rev = []
    for m in reversed(maps_seq):
        flat, valid = _np_gather(m)
        ib = valid[cur]
        rev.append((None if bool(ib.all()) else ib, float(m.fill), cur,
                    m.out_shape))
        cur = flat[cur]
    rev.reverse()
    levels = tuple((ok, fill, ew, p, shape)
                   for (ok, fill, p, shape), ew in zip(rev, ews))
    extras = []
    if sig.route_maps is not None:
        for b, m in enumerate(sig.route_maps):
            if b != sig.route_band:
                flat, valid = _np_gather(m)
                extras.append((flat, valid, float(m.fill), m.in_shape))
    return _Pulled(j=cur, in_shape=maps_seq[0].in_shape, levels=levels,
                   extras=tuple(extras))


def _program(sig: ChainSig, pulled: _Pulled) -> rw.RowProgram:
    """Row program: gathers for the source, each epilogue operand and each
    extra band; masks for each level and each band's validity."""
    seg = plan_segments(sig.out_shape, segment_bytes=sig.segment_bytes)
    rm = seg.rows * seg.minor
    lv_ok = [ok for ok, *_ in pulled.levels]

    def all_ok(oks):
        out = np.ones(rm, bool)
        for ok in oks:
            if ok is not None:
                out &= ok
        return out

    # (flat idx, needed elements, source shape, fill, exact validity?)
    wants = [(pulled.j, all_ok(lv_ok), pulled.in_shape, 0.0, None)]
    for li, (ok, fill, ew, p, shape) in enumerate(pulled.levels):
        if ew is not None:
            wants.append((p, all_ok(lv_ok[li + 1:]), shape, 0.0, None))
    for idx, valid, fill, shape in pulled.extras:
        wants.append((idx, valid, shape, fill, valid))

    def gathers_at(L):
        R = rm // L
        out = []
        for idx, need, shape, _, _ in wants:
            g = rw.row_gather(idx, need, shape, R, L)
            if g is None:
                return None
            out.append(g)
        return out

    found: dict[int, list] = {}

    def fits(L):
        found[L] = gathers_at(L)
        return found[L] is not None

    L = rw.choose_lanes(seg.minor, fits)
    gathers = found.get(L) or gathers_at(L)
    R = rm // L

    masks: list[rw.RowMask] = []
    ops: list[tuple] = [("load", 0)]
    g = 1
    for ok, fill, ew, p, shape in pulled.levels:
        if ok is not None:
            m = rw.row_mask(ok, R, L)
            if m is not None:
                masks.append(m)
                ops.append(("mask", len(masks) - 1, fill))
        if ew is not None:
            ops.append(("ew", ew, g))
            g += 1
    for idx, valid, fill, shape in pulled.extras:
        gk = gathers[g]
        exact = rw.implicit_mask(gk, R, L).reshape(-1)
        if sig.overlay:
            m = rw.row_mask(valid, R, L) or rw.RowMask(
                a=0, b=L, flags=np.ones(R, bool))
            masks.append(m)
            ops.append(("overlay", g, len(masks) - 1))
        elif np.array_equal(exact, valid):
            ops.append(("add", g))
        else:
            # the band copies lanes its map does not cover: re-fill them
            masks.append(rw.row_mask(valid, R, L))
            ops.append(("add_masked", g, len(masks) - 1, fill))
        g += 1

    fills = [w[3] for w in wants]
    slab_of = list(range(len(wants)))
    return rw.RowProgram(
        out_shape=sig.out_shape, dtype=sig.dtype, L=L,
        rb=seg.row_block * (seg.minor // L), grid=seg.n_segments,
        gathers=tuple(gathers), fills=tuple(fills), slab_of=tuple(slab_of),
        masks=tuple(masks), ops=tuple(ops), n_slabs=len(wants))


@lru_cache(maxsize=256)
def build_chain_plan(sig: ChainSig) -> ChainPlan:
    """Pull every link back onto the final output grid and lay the result
    out as one row program.

    Backward pass over the (coalesced) link maps: maintain ``cur``, the flat
    coordinate each final output element reads in the current link's output;
    each link contributes its validity (pulled back) and, when it carries an
    epilogue, the operand coordinates.  The result is exact: an element
    invalid at link ℓ takes link ℓ's fill and discards everything upstream —
    precisely the semantics of executing the links one by one.
    """
    links = _coalesce(sig.links)
    pulled = _pull_back(sig, links)
    return ChainPlan(sig=sig, program=_program(sig, pulled),
                     n_composed=len(sig.links) - len(links))


def chain_plan_of(sig: ChainSig) -> ChainPlan:
    """Expose the built plan (segments, composed count) for reports/tests
    without building or executing a kernel."""
    return build_chain_plan(sig)


def tpu_decline(sig: ChainSig) -> str | None:
    """Why the chain's kernel cannot launch on a TPU (None: it can)."""
    return rw.tpu_decline(build_chain_plan(sig).program)


def tm_chain(sig: ChainSig, x: jnp.ndarray,
             slabs: tuple[jnp.ndarray, ...] = (), *,
             interpret: bool, name: str = "tm_chain") -> jnp.ndarray:
    """Execute a chain signature: ``x`` is the chain source, ``slabs`` the
    epilogue operands then non-chain Route band sources, in link order.
    ``name`` is the launched kernel's family: ``tm_chain`` for a forwarding
    chain; gather mode and overlay routes pass their own."""
    prog = build_chain_plan(sig).program
    return rw.run(prog, (x,) + tuple(slabs), interpret=interpret, name=name)
