"""jaxpr -> TM IR front end.

Walks a traced jaxpr and pattern-matches tensor-manipulation equations into
:class:`~repro.core.instr.TMInstr` candidates, leaving everything else
(dot_general, conv, activations, …) as opaque :class:`~repro.compiler.ir.TPUNode`
equations.  Two match sources:

* **raw lax primitives** — transpose, reshape, squeeze, slice,
  dynamic_slice (constant starts), pad, concatenate, rev, broadcast_in_dim,
  copy, and same-shape elementwise add/sub/mul/max, each rebuilt as an exact
  :class:`~repro.core.affine.MixedRadixMap` (one TMU instruction's register
  contents);
* **tagged tm_ops** — inside :func:`repro.core.tm_primitive.tag_tm_ops`,
  the operator library binds ``tm_map`` / ``tm_route`` / ``tm_resize`` /
  ``tm_evaluate`` primitives whose params carry the exact map, so the match
  is trivial and lossless.

``jit`` sub-jaxprs are inlined when (and only when) they contain matchable
equations — ``jnp.pad``/``jnp.flip`` wrap their primitives in jit — so the
matcher sees through jnp's convenience wrappers without exploding opaque
compute into per-eqn nodes.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import math
import threading
import weakref

import jax
import numpy as np
from jax.extend.core import Literal

from repro.core import affine as af
from repro.core.affine import MixedRadixMap, batch_extend_map
from repro.core.instr import EwOp, RMEConfig, TMInstr, TMOpcode
from repro.compiler.ir import Buffer, TMGraph, TMNode, TPUNode, eval_tpu_node

# all-constant opaque eqns fold at trace time up to this output size — this
# is how scalar preprocessing (e.g. jnp.pad's convert_element_type on the pad
# value) becomes a register constant the matchers can read
_CONST_FOLD_LIMIT = 1 << 20

# weight-only TM work above that limit (the transpose of a tied embedding)
# is hoisted: folded once per source const array and shared by every
# program compiled over the same arrays.  Keys hold the sources' ids (jax
# Arrays are unhashable); a weakref.finalize on each source drops its
# entries when the weight goes.  The lock keeps two concurrent compiles
# from folding two copies.
_HOISTED: dict = {}  # key -> (weakrefs to the sources, folded outputs)
_HOIST_LOCK = threading.Lock()

_EW_PRIMS = {"add": EwOp.ADD, "sub": EwOp.SUB, "mul": EwOp.MUL,
             "max": EwOp.MAX}

# primitives the matcher may claim (used for the jit-inlining decision)
_TM_PRIM_NAMES = frozenset({
    "transpose", "reshape", "squeeze", "slice", "dynamic_slice",
    "dynamic_update_slice", "gather", "pad",
    "concatenate", "rev", "broadcast_in_dim", "copy",
    "reduce_window_max", "reduce_window_min", "reduce_window_sum",
    "tm_map", "tm_route", "tm_resize", "tm_evaluate",
}) | frozenset(_EW_PRIMS)

# irregular (non-arithmetic-progression) gather indices decompose into one
# Route band per index; past this count the band loop costs more than the
# XLA gather it replaces, so the matcher declines
_GATHER_MAX_BANDS = 64


def _aval_shape(v) -> tuple[int, ...]:
    return tuple(int(d) for d in v.aval.shape)


def _is_matchable(eqn, strict: bool = False) -> bool:
    """Cheap shape-level predicate: could :func:`_match_tm` claim this eqn?

    ``strict`` is the jit-inlining mode: a ``dynamic_slice`` counts only
    when its starts are Literals, because a traced start can never match —
    inlining a jit on its account would explode one opaque XLA call into
    per-eqn TPU nodes for nothing.  (At top level the gate stays permissive:
    ``_match_tm``'s ``get_const`` also resolves const-folded starts.)"""
    name = eqn.primitive.name
    if name not in _TM_PRIM_NAMES:
        return False
    if name in _EW_PRIMS:
        shapes = [_aval_shape(v) for v in eqn.invars]
        return (len(shapes) == 2 and shapes[0] == shapes[1]
                and len(shapes[0]) >= 1
                and eqn.invars[0].aval.dtype == eqn.invars[1].aval.dtype)
    if name == "dynamic_slice" and strict:
        return all(isinstance(v, Literal) for v in eqn.invars[1:])
    if name == "dynamic_update_slice" and strict:
        return all(isinstance(v, Literal) for v in eqn.invars[2:])
    return True


def _contains_tm(jaxpr) -> bool:
    for eqn in jaxpr.eqns:
        if _is_matchable(eqn, strict=True):
            return True
        if eqn.primitive.name == "jit" and _contains_tm(eqn.params["jaxpr"].jaxpr):
            return True
    return False


class _MatchFallback(Exception):
    """A matcher declining with an explanation: the eqn stays an opaque TPU
    node and the reason lands in ``TMGraph.notes`` (pass-report surface)."""


# ---------------------------------------------------------------------------
# per-eqn matchers: eqn -> TMInstr ingredients (maps / rme / ew) or None
# ---------------------------------------------------------------------------

def _match_tm(eqn, get_const):
    """Return a dict describing the TM instruction, or None to stay opaque.

    ``get_const(var)`` returns the concrete value of a constant operand (or
    None when the operand is a traced variable).
    """
    name = eqn.primitive.name
    in_shapes = [_aval_shape(v) for v in eqn.invars]
    out_shape = _aval_shape(eqn.outvars[0])

    if name == "tm_map":
        m = MixedRadixMap.decode(json.loads(eqn.params["map_json"]))
        b = eqn.params["batch_dims"]
        if b:  # lift over the leading batch axes: the graph runs at rank
            m = batch_extend_map(m, tuple(in_shapes[0][:b]))
        return {"map": m}
    if name == "tm_route":
        maps = [MixedRadixMap.decode(json.loads(s))
                for s in eqn.params["maps_json"]]
        b = eqn.params["batch_dims"]
        if b:
            maps = [batch_extend_map(m, tuple(s[:b]))
                    for m, s in zip(maps, in_shapes)]
        return {"maps": tuple(maps)}
    if name == "tm_resize":
        return {"resize": {"out_h": eqn.params["out_h"],
                           "out_w": eqn.params["out_w"],
                           "batch_dims": len(in_shapes[0]) - 3}}
    if name == "tm_evaluate":
        # batch_dims is deliberately left unset: the rme-legalize pass pins
        # it from the buffer shapes (and targets the batched kernel)
        p = eqn.params
        return {"rme": RMEConfig(scheme="evaluate", threshold=p["threshold"],
                                 cmp=p["cmp"], score_index=p["score_index"],
                                 capacity=p["capacity"])}

    if name == "transpose":
        return {"map": af.axis_permutation_map(in_shapes[0],
                                               eqn.params["permutation"])}
    if name in ("reshape", "squeeze"):
        if name == "reshape" and eqn.params.get("dimensions") is not None:
            return None  # fortran-order reshape: leave opaque
        m = af.reshape_map(in_shapes[0], out_shape)
        return {"map": m} if m is not None else None
    if name == "slice":
        starts = eqn.params["start_indices"]
        strides = eqn.params["strides"] or (1,) * len(starts)
        return {"map": af.strided_slice_map(in_shapes[0], starts, strides,
                                            out_shape)}
    if name == "dynamic_slice":
        starts = []
        for v in eqn.invars[1:]:
            c = v.val if isinstance(v, Literal) else get_const(v)
            if c is None:
                # traced start index: no register constant to fold into the
                # map's offsets — stay an opaque TPU phase (noted, not fatal)
                raise _MatchFallback(
                    "dynamic_slice: non-constant start index left opaque "
                    "(runtime starts cannot become TMU register offsets)")
            starts.append(int(c))
        sizes = tuple(int(s) for s in eqn.params["slice_sizes"])
        # lax.dynamic_slice clamps each start so the window stays in bounds
        starts = tuple(max(0, min(st, dim - sz))
                       for st, dim, sz in zip(starts, in_shapes[0], sizes))
        return {"map": af.strided_slice_map(in_shapes[0], starts,
                                            (1,) * len(sizes), out_shape),
                "keep_srcs": 1}  # start operands folded into the map offsets
    if name == "dynamic_update_slice":
        # invars: operand, update, *starts.  A Literal operand/update would
        # misalign the band->src pairing (srcs keeps only non-Literals)
        if any(isinstance(v, Literal) for v in eqn.invars[:2]):
            return None
        starts = []
        for v in eqn.invars[2:]:
            c = v.val if isinstance(v, Literal) else get_const(v)
            if c is None:
                raise _MatchFallback(
                    "dynamic_update_slice: non-constant start index left "
                    "opaque (runtime starts cannot become TMU register "
                    "offsets; bucket the position like a shape instead)")
            starts.append(int(c))
        upd = in_shapes[1]
        # lax clamps each start so the update window stays in bounds
        starts = tuple(max(0, min(st, dim - sz))
                       for st, dim, sz in zip(starts, in_shapes[0], upd))
        return {"maps": af.update_slice_maps(in_shapes[0], upd, starts),
                "overlay": True, "keep_srcs": 2}
    if name == "gather":
        return _match_gather(eqn, get_const, in_shapes, out_shape)
    if name in ("reduce_window_max", "reduce_window_min",
                "reduce_window_sum"):
        p = eqn.params
        if (any(int(w) != 1 for w in p["window_dimensions"])
                or any(int(x) != 1 for x in p["base_dilation"])
                or any(int(x) != 1 for x in p["window_dilation"])
                or any(int(l) != 0 or int(h) != 0 for l, h in p["padding"])):
            return None  # genuine windowed reduction: compute, not movement
        strides = tuple(int(s) for s in p["window_strides"])
        return {"map": af.strided_slice_map(in_shapes[0],
                                            (0,) * len(strides), strides,
                                            out_shape)}
    if name == "pad":
        cfg = eqn.params["padding_config"]
        if any(int(i) != 0 for _, _, i in cfg):
            return None  # interior (dilating) pad: leave opaque
        pv = eqn.invars[1]
        if isinstance(pv, Literal):
            fill = pv.val
        else:
            fill = get_const(pv)
            if fill is None:
                return None  # runtime pad value: not a register constant
        return {"map": af.pad_map(in_shapes[0],
                                  [int(lo) for lo, _, _ in cfg],
                                  [int(hi) for _, hi, _ in cfg],
                                  fill=float(fill)),
                "keep_srcs": 1}  # the pad value is folded into the map's fill
    if name == "concatenate":
        axis = int(eqn.params["dimension"])
        if any(isinstance(v, Literal) for v in eqn.invars):
            return None
        return {"maps": tuple(af.concat_maps(in_shapes, axis))}
    if name == "rev":
        return {"map": af.flip_map(in_shapes[0], eqn.params["dimensions"])}
    if name == "broadcast_in_dim":
        if len(in_shapes[0]) == 0 or math.prod(in_shapes[0]) <= 1:
            return None  # scalar/one-element broadcast: cheaper left to XLA
        if eqn.params.get("sharding") is not None:
            return None
        return {"map": af.broadcast_map(in_shapes[0], out_shape,
                                        eqn.params["broadcast_dimensions"])}
    if name == "copy":
        return {"copy": True}
    if name in _EW_PRIMS:
        if (len(in_shapes) == 2 and in_shapes[0] == in_shapes[1]
                and len(in_shapes[0]) >= 1
                and not any(isinstance(v, Literal) for v in eqn.invars)
                and eqn.invars[0].aval.dtype == eqn.invars[1].aval.dtype):
            return {"ew": _EW_PRIMS[name]}
        return None
    return None


def _match_gather(eqn, get_const, in_shapes, out_shape):
    """``jnp.take(x, idx, axis)``-form gathers with trace-constant indices.

    Supported form: one index axis (``start_index_map == collapsed_slice_dims
    == (axis,)``), full slices elsewhere, no batching dims, the taken axis
    landing back at ``axis`` in the output.  Regularly spaced indices become
    ONE strided map (:func:`~repro.core.affine.index_select_map`); irregular
    index vectors decompose into a band-per-index Route
    (:func:`~repro.core.affine.index_select_band_maps`) reading the operand
    once per band.  Traced indices degrade to an opaque TPU phase."""
    if isinstance(eqn.invars[0], Literal):
        return None  # srcs keeps non-Literals only: operand must be a var
    d = eqn.params["dimension_numbers"]
    if d.operand_batching_dims or d.start_indices_batching_dims:
        return None
    if (len(d.start_index_map) != 1
            or tuple(d.start_index_map) != tuple(d.collapsed_slice_dims)):
        return None
    axis = int(d.start_index_map[0])
    operand = in_shapes[0]
    nd = len(operand)
    sizes = tuple(int(s) for s in eqn.params["slice_sizes"])
    if len(sizes) != nd or sizes[axis] != 1 or any(
            sizes[i] != operand[i] for i in range(nd) if i != axis):
        return None
    if tuple(int(x) for x in d.offset_dims) != tuple(
            i for i in range(len(out_shape)) if i != axis):
        return None
    iv = eqn.invars[1]
    idx = iv.val if isinstance(iv, Literal) else get_const(iv)
    if idx is None:
        raise _MatchFallback(
            "gather: traced index vector left opaque (runtime indices "
            "cannot become TMU register contents)")
    idx = np.asarray(idx)
    if idx.ndim == 2 and idx.shape[1] == 1:
        idx = idx[:, 0]
    if idx.ndim != 1 or idx.shape[0] == 0:
        return None
    vals = [int(v) for v in idx]
    n = len(vals)
    if out_shape != tuple(n if i == axis else operand[i] for i in range(nd)):
        return None
    if not all(0 <= v < operand[axis] for v in vals):
        return None  # out-of-range indices read lax's fill value: leave to XLA
    step = vals[1] - vals[0] if n > 1 else 0
    if all(vals[j] == vals[0] + j * step for j in range(n)):
        return {"map": af.index_select_map(operand, axis, vals[0], step, n),
                "keep_srcs": 1}
    if n > _GATHER_MAX_BANDS:
        raise _MatchFallback(
            f"gather: {n} irregular indices exceed the "
            f"{_GATHER_MAX_BANDS}-band Route budget")
    return {"maps": tuple(af.index_select_band_maps(operand, axis, vals)),
            "keep_srcs": 1, "repeat_src": n}


# ---------------------------------------------------------------------------
# the walk
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Hoist:
    """One weight-only TM eqn folded into a graph const above the limit;
    ``shared`` when another program over the same consts folded it first."""

    dst: str
    prim: str
    nbytes: int
    shared: bool


def _nbytes(v) -> int:
    return math.prod(_aval_shape(v)) * np.dtype(v.aval.dtype).itemsize


def _hoistable(eqn) -> bool:
    """Weight-only TM work worth holding resident: at least one const
    array operand, and a result at most twice the bytes of the const
    arrays it reads (no broadcast turns a weight into a larger buffer).
    The caller has checked that every operand is a literal or a const."""
    arrays = {v for v in eqn.invars if not isinstance(v, Literal)}
    return bool(arrays) and (sum(_nbytes(v) for v in eqn.outvars)
                             <= 2 * sum(_nbytes(v) for v in arrays))


class _Builder:
    def __init__(self):
        self._n = itertools.count()
        self.nodes: list = []
        self.buffers: dict[str, Buffer] = {}
        self.consts: dict = {}
        self.matched: set[str] = set()
        self.notes: list[str] = []
        self.hoisted: list[Hoist] = []

    def fresh(self, prefix: str = "v") -> str:
        return f"{prefix}{next(self._n)}"

    def declare(self, name: str, shape, dtype) -> str:
        self.buffers[name] = Buffer(name, tuple(int(d) for d in shape), dtype)
        return name

    def const_buffer(self, val) -> str:
        name = self.fresh("c")
        self.declare(name, getattr(val, "shape", ()),
                     getattr(val, "dtype", type(val)))
        self.consts[name] = val
        return name

    def operand(self, v, env) -> str:
        if isinstance(v, Literal):
            return self.const_buffer(v.val)
        return env[v]

    def hoist(self, node: TPUNode) -> None:
        """Fold weight-only TM work into graph consts, through the memo."""
        eqn = node.eqn
        srcs = [self.consts[s] for s in node.srcs]
        key = (eqn.primitive.name, repr(sorted(eqn.params.items())),
               tuple(None if s is None else id(self.consts[s])
                     for s in node.src_names),
               tuple(repr(lit) for lit in node.literals))
        with _HOIST_LOCK:
            hit = _HOISTED.get(key)
            shared = hit is not None and all(
                r() is v for r, v in zip(hit[0], srcs))
            if shared:
                outs = hit[1]
            else:
                env = {s: self.consts[s] for s in node.srcs}
                eval_tpu_node(node, env)
                outs = tuple(env[d] for d in node.dst_names)
                # only immutable sources share: a numpy array folds unshared
                if all(isinstance(v, jax.Array) for v in srcs):
                    _HOISTED[key] = (tuple(weakref.ref(v) for v in srcs),
                                     outs)
                    for v in srcs:
                        weakref.finalize(v, _HOISTED.pop, key, None)
        for d, val, ov in zip(node.dst_names, outs, eqn.outvars):
            self.consts[d] = val
            self.hoisted.append(Hoist(d, eqn.primitive.name, _nbytes(ov),
                                      shared))


def _walk(builder: _Builder, jaxpr, consts, env) -> None:
    for cv, cval in zip(jaxpr.constvars, consts):
        env[cv] = builder.const_buffer(cval)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "jit" and _contains_tm(eqn.params["jaxpr"].jaxpr):
            inner = eqn.params["jaxpr"]
            sub_env = {}
            for iv, ov in zip(inner.jaxpr.invars, eqn.invars):
                sub_env[iv] = builder.operand(ov, env)
            _walk(builder, inner.jaxpr, inner.consts, sub_env)
            for outer_v, inner_v in zip(eqn.outvars, inner.jaxpr.outvars):
                env[outer_v] = (builder.const_buffer(inner_v.val)
                                if isinstance(inner_v, Literal)
                                else sub_env[inner_v])
            continue

        def get_const(v):
            if isinstance(v, Literal):
                return v.val
            buf = env.get(v)
            return builder.consts.get(buf) if buf is not None else None

        # trace-time constant folding wins over matching: an all-constant
        # eqn becomes a register constant downstream matchers can *read*
        # (e.g. the index-preprocessing chain inside jnp.take's jit must
        # fold so the gather matcher sees a constant index vector) — a
        # matched TM node would hide the value behind a buffer name
        constant = all(isinstance(v, Literal) or env[v] in builder.consts
                       for v in eqn.invars)
        foldable = constant and all(
            math.prod(_aval_shape(ov)) <= _CONST_FOLD_LIMIT
            for ov in eqn.outvars)

        match = None
        if _is_matchable(eqn) and not foldable:
            try:
                match = _match_tm(eqn, get_const)
            except _MatchFallback as note:
                builder.notes.append(str(note))
            except Exception as e:  # noqa: BLE001 — a matcher bug or shape
                # edge must degrade the eqn to an opaque TPU node, never kill
                # the whole trace; the note makes the residue explainable
                builder.notes.append(
                    f"{name}: matcher error left opaque ({e!r})")
        # above the fold limit, TM work the matcher claims on weights alone
        # is hoisted: it runs once here instead of as a TM phase per call
        hoist = match is not None and constant and _hoistable(eqn)
        if match is not None and not hoist and any(not isinstance(v, Literal)
                                     for v in eqn.invars):
            srcs = tuple(builder.operand(v, env) for v in eqn.invars
                         if not isinstance(v, Literal))
            if "keep_srcs" in match:
                srcs = srcs[:match["keep_srcs"]]
            if "repeat_src" in match:  # band-per-index gather: every Route
                #                        band reads the same operand buffer
                srcs = (srcs[0],) * match["repeat_src"]
            ov = eqn.outvars[0]
            dst = builder.fresh()
            builder.declare(dst, ov.aval.shape, ov.aval.dtype)
            env[ov] = dst
            builder.matched.add(name)
            builder.nodes.append(TMNode(_build_instr(match, srcs, dst),
                                        matched=name))
            continue

        # opaque TPU node
        src_names = tuple(None if isinstance(v, Literal) else env[v]
                          for v in eqn.invars)
        literals = tuple(v.val if isinstance(v, Literal) else None
                         for v in eqn.invars)
        dsts = []
        for ov in eqn.outvars:
            d = builder.fresh()
            builder.declare(d, ov.aval.shape, ov.aval.dtype)
            env[ov] = d
            dsts.append(d)
        node = TPUNode(eqn=eqn, src_names=src_names, literals=literals,
                       dst_names=tuple(dsts))
        if foldable:  # trace-time constant folding: the value becomes a
            #           register constant downstream matchers can read
            eval_tpu_node(node, builder.consts)
            continue
        if hoist:
            builder.hoist(node)
            continue
        builder.nodes.append(node)


def _build_instr(match: dict, srcs: tuple[str, ...], dst: str) -> TMInstr:
    if "map" in match:
        return TMInstr(TMOpcode.COARSE, srcs, dst, map_=match["map"])
    if "maps" in match:
        meta = {"overlay": True} if match.get("overlay") else None
        return TMInstr(TMOpcode.COARSE, srcs, dst, maps=match["maps"],
                       meta=meta)
    if "ew" in match:
        return TMInstr(TMOpcode.ELEMENTWISE, srcs, dst, ew=match["ew"])
    if "resize" in match:
        r = match["resize"]
        return TMInstr(TMOpcode.RESIZE, srcs, dst,
                       meta={"out_h": r["out_h"], "out_w": r["out_w"],
                             "batch_dims": r["batch_dims"]})
    if "rme" in match:
        return TMInstr(TMOpcode.FINE_EVALUATE, srcs, dst, rme=match["rme"])
    if "copy" in match:
        return TMInstr(TMOpcode.COPY, srcs, dst)
    raise AssertionError(match)


def graph_from_jaxpr(closed_jaxpr) -> TMGraph:
    """Lower a ClosedJaxpr (from ``jax.make_jaxpr``) into a :class:`TMGraph`."""
    jaxpr = closed_jaxpr.jaxpr
    builder = _Builder()
    env = {}
    inputs = []
    for v in jaxpr.invars:
        n = builder.fresh("in")
        builder.declare(n, v.aval.shape, v.aval.dtype)
        env[v] = n
        inputs.append(n)
    _walk(builder, jaxpr, closed_jaxpr.consts, env)
    outputs = tuple(builder.operand(v, env) for v in jaxpr.outvars)
    graph = TMGraph(nodes=builder.nodes, buffers=builder.buffers,
                    inputs=tuple(inputs), outputs=outputs,
                    consts=builder.consts, matched_prims=builder.matched,
                    notes=builder.notes, hoisted=builder.hoisted)
    graph.validate()
    return graph
