"""Bilinear Resize Pallas kernel (fine-grained TM, paper Fig. 2b).

The RME view of Resize: each output pixel *assembles* four neighbouring
input elements and *evaluates* their weighted average.  TPU-native form:
tap indices and fractional weights are precomputed per output row/col at
trace time (they fold to constants — the masking-register contents), and the
kernel performs two gathers + fused multiply-adds per block, entirely in
VMEM.  Grid over output-row blocks; the input slab stays VMEM-resident.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.platform import pallas_interpret


def _resize_kernel(x_ref, y0_ref, y1_ref, wy_ref, x0_ref, x1_ref, wx_ref, o_ref):
    x = x_ref[...]              # (H, W, C) slab
    y0, y1 = y0_ref[...], y1_ref[...]
    x0, x1 = x0_ref[...], x1_ref[...]
    wy = wy_ref[...][:, None, None]
    wx = wx_ref[...][None, :, None]
    top_rows = jnp.take(x, y0, axis=0)      # (bh, W, C)
    bot_rows = jnp.take(x, y1, axis=0)
    v00 = jnp.take(top_rows, x0, axis=1)    # (bh, OW, C)
    v01 = jnp.take(top_rows, x1, axis=1)
    v10 = jnp.take(bot_rows, x0, axis=1)
    v11 = jnp.take(bot_rows, x1, axis=1)
    top = v00 * (1 - wx) + v01 * wx
    bot = v10 * (1 - wx) + v11 * wx
    o_ref[...] = (top * (1 - wy) + bot * wy).astype(o_ref.dtype)


def resize_bilinear(x: jnp.ndarray, out_h: int, out_w: int, *,
                    row_block: int = 32, interpret: bool | None = None) -> jnp.ndarray:
    """(H, W, C) -> (out_h, out_w, C), half-pixel convention."""
    H, W, C = x.shape
    ys = (jnp.arange(out_h, dtype=jnp.float32) + 0.5) * (H / out_h) - 0.5
    xs = (jnp.arange(out_w, dtype=jnp.float32) + 0.5) * (W / out_w) - 0.5
    y0 = jnp.clip(jnp.floor(ys), 0, H - 1).astype(jnp.int32)
    x0 = jnp.clip(jnp.floor(xs), 0, W - 1).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, H - 1)
    x1 = jnp.minimum(x0 + 1, W - 1)
    wy = jnp.clip(ys - y0, 0.0, 1.0)
    wx = jnp.clip(xs - x0, 0.0, 1.0)
    rb = math.gcd(out_h, row_block)
    grid = (out_h // rb,)
    return pl.pallas_call(
        _resize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((H, W, C), lambda i: (0, 0, 0)),
            pl.BlockSpec((rb,), lambda i: (i,)),
            pl.BlockSpec((rb,), lambda i: (i,)),
            pl.BlockSpec((rb,), lambda i: (i,)),
            pl.BlockSpec((out_w,), lambda i: (0,)),
            pl.BlockSpec((out_w,), lambda i: (0,)),
            pl.BlockSpec((out_w,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rb, out_w, C), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((out_h, out_w, C), x.dtype),
        name="resize",
        interpret=(pallas_interpret(x) if interpret is None
                   else interpret),
    )(x, y0, y1, wy, x0, x1, wx)
