"""Tiled matmul with TM-epilogue output forwarding (paper Fig. 5c).

The paper's output-forwarding strategy lets the TMU begin the next TM op on
*partial* TPU output tiles, before the producer finishes.  The exact TPU
analogue: apply the TM op inside the matmul's output path — the output
``BlockSpec.index_map`` places each finished tile directly at its
TM-transformed destination, and an optional ``local_fn`` reshapes the tile
in-register before the store.  The manipulation therefore completes the
moment the matmul does: zero extra HBM round-trips, zero added latency.

Supported epilogues (decoded from a MixedRadixMap, or given explicitly):
  * block placement — out tile (i, j) stored at block f(i, j) (Transpose/
    Split/Route-band class)
  * local transform — in-VMEM reshape/transpose of the tile (PixelShuffle
    class: row y of (W, C·s²) becomes the (s, W·s, C) image slab at row y·s)
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret


def block_div(n: int, b: int) -> int:
    """Largest block size <= ``b`` that divides ``n`` (>= 1) — the divisor
    clamp the wrappers apply so odd dims never hand Pallas a grid whose
    blocks don't tile the array."""
    b = max(1, min(int(b), int(n)))
    while n % b:
        b -= 1
    return b


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int,
               local_fn: Callable | None):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _commit():
        tile = acc_ref[...].astype(o_ref.dtype)
        if local_fn is not None:
            tile = local_fn(tile)  # in-register TM before the store
        o_ref[...] = tile


def matmul_tm(x: jnp.ndarray, w: jnp.ndarray, *,
              out_shape: tuple[int, ...] | None = None,
              out_index_map: Callable | None = None,
              out_block: tuple[int, ...] | None = None,
              local_fn: Callable | None = None,
              bm: int = 128, bn: int = 128, bk: int = 128,
              interpret: bool | None = None) -> jnp.ndarray:
    """``TM(x @ w)`` with the TM op folded into the output store path.

    Defaults to the identity epilogue (plain tiled matmul).  ``out_index_map``
    receives grid indices (i, j, k) and returns the output *block* index;
    ``local_fn`` maps the (bm, bn) f32 tile to the out-block shape.
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    if out_shape is None:
        out_shape = (M, N)
    if out_block is None:
        out_block = (bm, bn)
    if out_index_map is None:
        out_index_map = lambda i, j, k: (i, j)
    kern = functools.partial(_mm_kernel, nk=nk, local_fn=local_fn)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec(out_block, out_index_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="matmul_tm",
        interpret=(pallas_interpret(x) if interpret is None
                   else interpret),
    )(x, w)


# ---------------------------------------------------------------------------
# canned epilogues
# ---------------------------------------------------------------------------

def transpose_epilogue(M: int, N: int, bm: int, bn: int):
    """out = (x @ w)^T, written transposed at tile-commit time."""
    return dict(
        out_shape=(N, M), out_block=(bn, bm),
        out_index_map=lambda i, j, k: (j, i),
        local_fn=lambda t: t.T,
    )


def pixel_shuffle_epilogue(H: int, W: int, C: int, s: int):
    """Producer rows are image rows: x (H·W? no — H rows of W pixels) @ w
    giving (W, C·s²) per grid row i; committed as the (s, W·s, C) slab at
    image row i·s.  Requires bm == W, bn == C·s² (one image row per tile).
    """
    def local(tile):  # (W, C·s²) -> (s, W·s, C)
        W_, Cs2 = tile.shape
        t = tile.reshape(W_, C, s, s)           # c, dy, dx  (c-major paper layout)
        t = t.transpose(2, 0, 3, 1)             # (dy, W, dx, C)
        return t.reshape(s, W_ * s, C)

    return dict(
        out_shape=(H * s, W * s, C), out_block=(s, W * s, C),
        out_index_map=lambda i, j, k: (i, 0, 0),
        local_fn=local,
    )


def split_epilogue(M: int, N: int, bm: int, bn: int, n_parts: int, part: int):
    """Commit only the ``part``-th channel band: out = split(x@w, n)[part].

    Grid j covers the band's columns only (caller slices w accordingly); the
    epilogue is the band placement."""
    return dict(
        out_shape=(M, N // n_parts), out_block=(bm, bn),
        out_index_map=lambda i, j, k: (i, j),
        local_fn=None,
    )
