"""Jit'd wrappers for matmul with TM-epilogue output forwarding."""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.core.affine import MixedRadixMap
from repro.kernels.matmul_tm.matmul_tm import (
    block_div, matmul_tm, pixel_shuffle_epilogue, transpose_epilogue)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_call(x, w, *, bm=128, bn=128, bk=128, interpret=None):
    M, K = x.shape
    N = w.shape[1]
    # divisor clamp, not just min: odd dims above the block default (e.g.
    # M=192 with bm=128) must still tile the array
    bm, bn, bk = block_div(M, bm), block_div(N, bn), block_div(K, bk)
    return matmul_tm(x, w, bm=bm, bn=bn, bk=bk, interpret=interpret)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_transpose_call(x, w, *, bm=128, bn=128, bk=128, interpret=None):
    M, K = x.shape
    N = w.shape[1]
    bm, bn, bk = block_div(M, bm), block_div(N, bn), block_div(K, bk)
    ep = transpose_epilogue(M, N, bm, bn)
    return matmul_tm(x, w, bm=bm, bn=bn, bk=bk, interpret=interpret, **ep)


@partial(jax.jit, static_argnames=("H", "W", "C", "s", "bk", "interpret"))
def matmul_pixel_shuffle_call(x, w, *, H, W, C, s, bk=128, interpret=None):
    """(H·W, K) @ (K, C·s²) committed directly as the (H·s, W·s, C) image."""
    K = x.shape[1]
    ep = pixel_shuffle_epilogue(H, W, C, s)
    return matmul_tm(x, w, bm=W, bn=C * s * s, bk=block_div(K, bk),
                     interpret=interpret, **ep)


@lru_cache(maxsize=128)
def _dot_node(M: int, K: int, N: int, dtype_str: str):
    """A synthesized TPUNode for the canonical 2D dot — what routes
    ``matmul_tm_call`` through the cross-engine chain registry."""
    from repro.compiler.ir import TPUNode
    dt = jnp.dtype(dtype_str)
    jaxpr = jax.make_jaxpr(
        lambda a, b: jax.lax.dot_general(a, b, (((1,), (0,)), ((), ()))))(
        jax.ShapeDtypeStruct((M, K), dt), jax.ShapeDtypeStruct((K, N), dt))
    return TPUNode(eqn=jaxpr.jaxpr.eqns[0], src_names=("a", "b"),
                   literals=(None, None), dst_names=("y",))


def matmul_tm_call(x: jnp.ndarray, w: jnp.ndarray, m: MixedRadixMap, *,
                   interpret: bool | None = None) -> jnp.ndarray:
    """Generic entry: ``m(x @ w)`` as ONE launch via the cross-engine chain
    registry (the matmul commits through the composed chain map), with the
    bespoke transpose epilogue kept for its exact case, and matmul followed
    by the generic tm_affine kernel (two passes) only as the decline
    branch."""
    from repro.core.dispatch import lower_xengine
    from repro.core.instr import TMInstr, TMOpcode
    from repro.kernels.tm_affine.ops import tm_affine_call
    if m.is_pure_permutation() and m.permutation() == (1, 0):
        return matmul_transpose_call(x, w, interpret=interpret)
    M, K = x.shape
    N = w.shape[1]
    if x.dtype == w.dtype and m.in_shape == (M, N):
        node = _dot_node(M, K, N, str(x.dtype))
        ins = TMInstr(opcode=TMOpcode.COARSE, srcs=("y",), dst="z", map_=m)
        lowered = lower_xengine("compute_to_tm", node, [x, w], [ins],
                                [[None]])
        if lowered is not None:
            return lowered[0]
    y = matmul_call(x, w, interpret=interpret)
    return tm_affine_call(y, m, interpret=interpret)
