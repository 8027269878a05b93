"""Plain reference of ``yolov3tiny-448``: darknet's yolov3-tiny in float32
with every convolution at ``highest`` precision, and the detect tail in
NumPy.

It imports nothing of the program and takes the benchmark's own weights.
``precision="high"`` is the control: the same network with each convolution
as three bfloat16 passes (hi x hi + hi x lo + lo x hi, accumulated in
float32), the precision step below the configuration's.  A TPU runs them as
``Precision.HIGH``; elsewhere, where XLA ignores ``precision``, they are
spelled out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

def _conv32(x, w, precision=jax.lax.Precision.HIGHEST):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=precision)


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _conv(x, w, precision):
    if precision == "highest":
        return _conv32(x, w)
    if jax.default_backend() == "tpu":
        return _conv32(x, w, jax.lax.Precision.HIGH)
    (xh, xl), (wh, wl) = _split(x), _split(w)
    return _conv32(xh, wh) + _conv32(xh, wl) + _conv32(xl, wh)


def _leaky(x, slope):
    return jnp.where(x >= 0, x, slope * x)


def _pool(x, stride):
    """2x2 max pool; at stride 1 the window past the last row and column
    sees only what lies inside, as darknet's does."""
    if stride == 2:
        B, H, W, C = x.shape
        return x.reshape(B, H // 2, 2, W // 2, 2, C).max(axis=(2, 4))
    p = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)),
                constant_values=-jnp.inf)
    return jnp.maximum(jnp.maximum(p[:, :-1, :-1], p[:, 1:, :-1]),
                       jnp.maximum(p[:, :-1, 1:], p[:, 1:, 1:]))


@functools.partial(jax.jit, static_argnames=("precision", "layout"))
def grids(w, img, *, layout, precision: str = "highest"):
    """The two raw head grids of ``img`` (B, H, W, 3).  ``layout`` is
    (input channels after the pad, pool strides, routed layer, slope)."""
    if precision not in ("highest", "high"):
        raise ValueError(f"unknown precision {precision!r}")
    channels, strides, route, slope = layout

    def conv(x, p, act=True):
        y = _conv(x, p["w"], precision) + p["b"]
        return _leaky(y, slope) if act else y

    x = jnp.pad(img, ((0, 0), (0, 0), (0, 0), (0, channels - img.shape[-1])))
    for i, (p, s) in enumerate(zip(w["backbone"], strides)):
        x = conv(x, p)
        if i == route:
            routed = x
        x = _pool(x, s)
    x = conv(x, w["conv7"])
    r = conv(x, w["head1_reduce"])
    pred1 = conv(conv(r, w["head1"]), w["head1_out"], act=False)
    u = conv(r, w["up_reduce"])
    u = jnp.repeat(jnp.repeat(u, 2, axis=1), 2, axis=2)
    x = conv(jnp.concatenate([u, routed], -1), w["head2"])
    pred2 = conv(x, w["head2_out"], act=False)
    return pred1, pred2


def detect_tail(grid: np.ndarray, threshold: float, capacity: int,
                score_index: int) -> np.ndarray:
    """Records (rows of 5 + classes) of a raw grid (B, Hg, Wg, 3 d) whose
    score is at least ``threshold``, packed in row order into ``capacity``
    rows per image, the rest zero."""
    B, Hg, Wg, no = grid.shape
    rows = grid.reshape(B, Hg * Wg * 3, no // 3)
    out = np.zeros((B, capacity, no // 3), grid.dtype)
    for b in range(B):
        keep = rows[b][rows[b][:, score_index] >= threshold][:capacity]
        out[b, :len(keep)] = keep
    return out
