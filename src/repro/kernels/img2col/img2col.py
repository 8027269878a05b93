"""Img2col Pallas kernel + implicit-GEMM convolution.

Paper context: Img2col is the TM op the in-house TPU's MTE accelerates — it
prepares activation buffers for the systolic array, and accounts for much of
EDSR's 40.62% TM share.  On TPU the near-memory form is *implicit GEMM*: the
patch matrix is never materialized in HBM; each conv kernel grid step builds
its patch tile in VMEM from a (kh + bm·stride) row slab and feeds the MXU
directly — Img2col runs inside the DMA path, exactly the paper's model.

Kernels:
  * ``img2col_call``  — standalone patch-matrix kernel (grid over output-row
    blocks; body assembles patches by static (ky, kx) slicing — no gathers).
  * ``conv2d_call``   — implicit-GEMM conv: patch assembly fused with the
    matmul; out (…, OH·OW, OC) = patches @ w.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.platform import pallas_interpret


def _im2col_rows(slab, oh_b, OW, kh, kw, C, stride):
    """Assemble (oh_b·OW, kh·kw·C) patches from a VMEM row slab.

    ``slab``: (kh + (oh_b-1)·stride, Wp, C) padded input rows.  Static loops
    over (ky, kx) — each tap is a strided slice, vectorized over (oy, ox).
    """
    taps = []
    for ky in range(kh):
        for kx in range(kw):
            rows = jax.lax.slice(
                slab,
                (ky, kx, 0),
                (ky + (oh_b - 1) * stride + 1, kx + (OW - 1) * stride + 1, C),
                (stride, stride, 1),
            )  # (oh_b, OW, C)
            taps.append(rows)
    pm = jnp.stack(taps, axis=2)  # (oh_b, OW, kh·kw, C)
    return pm.reshape(oh_b * OW, kh * kw * C)


def _img2col_kernel(x_ref, o_ref, *, oh_b, OW, kh, kw, C, stride):
    o_ref[...] = _im2col_rows(x_ref[...], oh_b, OW, kh, kw, C, stride)


def img2col(x: jnp.ndarray, kh: int, kw: int, stride: int = 1, pad: int = 0,
            *, oh_block: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """(H, W, C) -> (OH·OW, kh·kw·C). Padding applied on the host side once."""
    H, W, C = x.shape
    OH = (H + 2 * pad - kh) // stride + 1
    OW = (W + 2 * pad - kw) // stride + 1
    xp = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0))) if pad else x
    oh_b = math.gcd(OH, oh_block)
    slab_rows = kh + (oh_b - 1) * stride
    grid = (OH // oh_b,)
    kern = functools.partial(_img2col_kernel, oh_b=oh_b, OW=OW, kh=kh, kw=kw,
                             C=C, stride=stride)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec(
            (slab_rows, xp.shape[1], C),
            # element offset oy·stride expressed in slab_rows blocks requires
            # stride·oh_b == slab_rows; otherwise we pass overlapping blocks
            # via a block-index trick: index unit = oh_b·stride rows.
            lambda i: (i, 0, 0),
            # NOTE: overlapping windows — Pallas supports this when the block
            # index unit is the block shape; we instead re-tile below.
        )],
        out_specs=pl.BlockSpec((oh_b * OW, kh * kw * C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((OH * OW, kh * kw * C), x.dtype),
        name="img2col",
        interpret=(pallas_interpret(x) if interpret is None
                   else interpret),
    )(xp) if slab_rows == oh_b * stride else _img2col_overlap(
        xp, OH, OW, kh, kw, C, stride, oh_b, interpret)


def _img2col_overlap(xp, OH, OW, kh, kw, C, stride, oh_b, interpret):
    """Overlapping-slab variant: read each slab as a ``pl.ds`` window of a
    full-VMEM input (single-block in_spec), still assembling patches on-chip."""
    slab_rows = kh + (oh_b - 1) * stride

    def kernel(x_ref, o_ref):
        i = pl.program_id(0)
        slab = x_ref[pl.ds(i * oh_b * stride, slab_rows)]
        o_ref[...] = _im2col_rows(slab, oh_b, OW, kh, kw, C, stride)

    return pl.pallas_call(
        kernel,
        grid=(OH // oh_b,),
        in_specs=[pl.BlockSpec(xp.shape, lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((oh_b * OW, kh * kw * C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((OH * OW, kh * kw * C), xp.dtype),
        name="img2col",
        interpret=(pallas_interpret(xp) if interpret is None
                   else interpret),
    )(xp)


# ---------------------------------------------------------------------------
# implicit-GEMM convolution: img2col fused into the matmul (never in HBM)
# ---------------------------------------------------------------------------

def _conv_kernel(x_ref, w_ref, o_ref, *, oh_b, OW, kh, kw, C, stride):
    i = pl.program_id(0)
    slab_rows = kh + (oh_b - 1) * stride
    slab = x_ref[pl.ds(i * oh_b * stride, slab_rows)]
    patches = _im2col_rows(slab, oh_b, OW, kh, kw, C, stride)
    o_ref[...] = jnp.dot(patches, w_ref[...],
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def conv2d(x: jnp.ndarray, w: jnp.ndarray, stride: int = 1, pad: int = 0,
           *, oh_block: int = 8, interpret: bool | None = None) -> jnp.ndarray:
    """Implicit-GEMM conv.  x: (H, W, C); w: (kh, kw, C, OC) -> (OH, OW, OC)."""
    H, W, C = x.shape
    kh, kw, _, OC = w.shape
    OH = (H + 2 * pad - kh) // stride + 1
    OW = (W + 2 * pad - kw) // stride + 1
    xp = jnp.pad(x, ((pad, pad), (pad, pad), (0, 0))) if pad else x
    oh_b = math.gcd(OH, oh_block)
    wm = w.reshape(kh * kw * C, OC)
    kern = functools.partial(_conv_kernel, oh_b=oh_b, OW=OW, kh=kh, kw=kw,
                             C=C, stride=stride)
    out = pl.pallas_call(
        kern,
        grid=(OH // oh_b,),
        in_specs=[
            pl.BlockSpec(xp.shape, lambda i: (0, 0, 0)),
            pl.BlockSpec(wm.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((oh_b * OW, OC), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((OH * OW, OC), x.dtype),
        name="img2col",
        interpret=(pallas_interpret(x) if interpret is None
                   else interpret),
    )(xp, wm)
    return out.reshape(OH, OW, OC)
