"""JAX primitives that tag TM operators inside a jaxpr.

The compiler (:mod:`repro.compiler`) recovers TM instructions from a traced
program two ways: by pattern-matching raw lax primitives (transpose, reshape,
slice, pad, concatenate, rev, broadcast_in_dim, elementwise), and — for the
operators of :mod:`repro.core.tm_ops`, whose lowered form is an opaque gather
— by *tagging*: inside :func:`tag_tm_ops`, every tm_ops callable binds one of
the primitives below instead of executing, leaving a single eqn in the jaxpr
that carries the exact :class:`~repro.core.affine.MixedRadixMap` (serialized
in the params, the TMU's register contents).  Outside the tagging context the
ops execute normally, so nothing changes for eager/jit/grad users.

The primitives have concrete impls (the generic engine), so an untagged
evaluation of a tagged jaxpr still computes the right values — tagging never
changes semantics, only visibility.
"""

from __future__ import annotations

import contextlib
import json

import jax.core as jax_core
import jax.numpy as jnp
from jax.extend.core import Primitive
from jax.interpreters import batching, mlir

_TAGGING = False


def tagging() -> bool:
    """True inside a :func:`tag_tm_ops` context (compiler trace in progress)."""
    return _TAGGING


@contextlib.contextmanager
def tag_tm_ops():
    """Make tm_ops callables bind tagging primitives instead of executing."""
    global _TAGGING
    prev = _TAGGING
    _TAGGING = True
    try:
        yield
    finally:
        _TAGGING = prev


def _decode(map_json: str):
    from repro.core.affine import MixedRadixMap
    return MixedRadixMap.decode(json.loads(map_json))


def encode_map(m) -> str:
    """Hashable (eqn-params-safe) serialization of a MixedRadixMap."""
    return json.dumps(m.encode(), sort_keys=True)


# ---------------------------------------------------------------------------
# tm_map — one coarse-grained instruction (single gather map)
# ---------------------------------------------------------------------------

tm_map_p = Primitive("tm_map")


def _tm_map_impl(x, *, map_json: str, batch_dims: int):
    from repro.core.engine import apply_map
    return apply_map(_decode(map_json), x, batch_dims=batch_dims)


def _tm_map_abstract(x, *, map_json: str, batch_dims: int):
    m = _decode(map_json)
    return jax_core.ShapedArray(x.shape[:batch_dims] + m.out_shape, x.dtype)


tm_map_p.def_impl(_tm_map_impl)
tm_map_p.def_abstract_eval(_tm_map_abstract)
# XLA lowering = the impl: a tagged jaxpr that escapes into jit (e.g. the
# traced fn was itself jit-wrapped, caching the tagged form) still runs
mlir.register_lowering(tm_map_p, mlir.lower_fun(_tm_map_impl,
                                                multiple_results=False))


def bind_map(m, x, batch_dims: int = 0):
    return tm_map_p.bind(x, map_json=encode_map(m), batch_dims=batch_dims)


# vmap rule: move the mapped axis to the front and grow batch_dims — the
# serving batcher's vmap lift then reaches the compiler as the same
# batch_dims the trace matcher already lifts via batch_extend_map
def _tm_map_batcher(args, dims, *, map_json, batch_dims):
    (x,), (d,) = args, dims
    x = jnp.moveaxis(x, d, 0)
    return tm_map_p.bind(x, map_json=map_json,
                         batch_dims=batch_dims + 1), 0


batching.primitive_batchers[tm_map_p] = _tm_map_batcher


# ---------------------------------------------------------------------------
# tm_route — multi-band coarse instruction (Route / concat)
# ---------------------------------------------------------------------------

tm_route_p = Primitive("tm_route")


def _tm_route_impl(*xs, maps_json: tuple[str, ...], batch_dims: int):
    from repro.core.engine import route_gather
    maps = [_decode(s) for s in maps_json]
    return route_gather(maps, xs, batch_dims=batch_dims)


def _tm_route_abstract(*xs, maps_json: tuple[str, ...], batch_dims: int):
    m = _decode(maps_json[0])
    return jax_core.ShapedArray(xs[0].shape[:batch_dims] + m.out_shape,
                                xs[0].dtype)


tm_route_p.def_impl(_tm_route_impl)
tm_route_p.def_abstract_eval(_tm_route_abstract)
mlir.register_lowering(tm_route_p, mlir.lower_fun(_tm_route_impl,
                                                  multiple_results=False))


def bind_route(maps, xs, batch_dims: int = 0):
    return tm_route_p.bind(*xs, maps_json=tuple(encode_map(m) for m in maps),
                           batch_dims=batch_dims)


def _tm_route_batcher(args, dims, *, maps_json, batch_dims):
    size = next(x.shape[d] for x, d in zip(args, dims)
                if d is not batching.not_mapped)
    xs = [jnp.broadcast_to(x[None], (size,) + x.shape)
          if d is batching.not_mapped else jnp.moveaxis(x, d, 0)
          for x, d in zip(args, dims)]
    return tm_route_p.bind(*xs, maps_json=maps_json,
                           batch_dims=batch_dims + 1), 0


batching.primitive_batchers[tm_route_p] = _tm_route_batcher


# ---------------------------------------------------------------------------
# tm_resize — fine-grained bilinear Resize
# ---------------------------------------------------------------------------

tm_resize_p = Primitive("tm_resize")


def _tm_resize_impl(x, *, out_h: int, out_w: int):
    from repro.core.tm_ops import _resize_bilinear_impl
    return _resize_bilinear_impl(x, out_h, out_w)


def _tm_resize_abstract(x, *, out_h: int, out_w: int):
    return jax_core.ShapedArray(x.shape[:-3] + (out_h, out_w, x.shape[-1]),
                                x.dtype)


tm_resize_p.def_impl(_tm_resize_impl)
tm_resize_p.def_abstract_eval(_tm_resize_abstract)
mlir.register_lowering(tm_resize_p, mlir.lower_fun(_tm_resize_impl,
                                                   multiple_results=False))


# resize and evaluate operate on trailing core axes natively, so vmap is
# just "mapped axis to the front"
def _leading_axes_batcher(prim):
    def batcher(args, dims, **params):
        (x,), (d,) = args, dims
        return prim.bind(jnp.moveaxis(x, d, 0), **params), 0
    return batcher


batching.primitive_batchers[tm_resize_p] = _leading_axes_batcher(tm_resize_p)


# ---------------------------------------------------------------------------
# tm_evaluate — fine-grained RME evaluate (Bboxcal rows), leading batch axes
# ---------------------------------------------------------------------------

tm_evaluate_p = Primitive("tm_evaluate")


def _tm_evaluate_impl(x, *, threshold: float, capacity: int, cmp: str,
                      score_index: int):
    from repro.core.tm_ops import _bboxcal_rows_impl
    return _bboxcal_rows_impl(x, threshold, capacity, cmp, score_index)


def _tm_evaluate_abstract(x, *, threshold: float, capacity: int, cmp: str,
                          score_index: int):
    return jax_core.ShapedArray(x.shape[:-2] + (capacity, x.shape[-1]),
                                x.dtype)


tm_evaluate_p.def_impl(_tm_evaluate_impl)
tm_evaluate_p.def_abstract_eval(_tm_evaluate_abstract)
mlir.register_lowering(tm_evaluate_p, mlir.lower_fun(_tm_evaluate_impl,
                                                     multiple_results=False))
batching.primitive_batchers[tm_evaluate_p] = \
    _leading_axes_batcher(tm_evaluate_p)
