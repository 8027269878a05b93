"""Generic execution engine for the unified address abstraction.

``apply_map`` executes *any* :class:`~repro.core.affine.MixedRadixMap` on a
JAX array — this is the software model of the TMU's reconfigurable
address-generation datapath: one routine, parameterized by instruction fields
(splits / A / b / fill), executes every coarse-grained TM operator.  Adding a
new operator requires a new map, never new execution code (the paper's
reconfigurability claim, kept testable).

Exactness: affine rows with rational entries are evaluated as
``floor((Σ num_j·d_j + num_b) / L)`` with ``L`` the LCM of denominators —
bit-exact w.r.t. the Fraction oracle, including negative operands
(``jnp.floor_divide`` floors toward -inf like Python).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.affine import MixedRadixMap
from repro.core.spec import row_major_strides

# the element-wise stage's vector ops, keyed by EwOp.value — the single
# table shared by the reference executor and the Pallas kernel epilogues
EW_FNS = {"add": jnp.add, "sub": jnp.subtract, "mul": jnp.multiply,
          "max": jnp.maximum}


def _row_int_form(row, off) -> tuple[tuple[int, ...], int, int]:
    """(numerators, offset_numerator, common_denominator) for one affine row."""
    dens = [a.denominator for a in row] + [off.denominator]
    L = 1
    for d in dens:
        L = L * d // math.gcd(L, d)
    nums = tuple(int(a * L) for a in row)
    return nums, int(off * L), L


def gather_indices(m: MixedRadixMap, xp=jnp) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Flat input index + validity mask for every output element.

    Returns ``(flat_idx, valid)`` of shape ``m.out_shape`` (int32 / bool).
    Traced with concrete shapes — everything here folds to constants under
    jit; on TPU the index tensors are computed on-device from iota (no host
    transfer), exactly like the TMU's runtime address generator.  With
    ``xp=numpy`` the same arithmetic runs on the host (kernel address
    tables are built that way, without a device dispatch per operation).
    """
    nd_out = len(m.out_shape)
    if xp is jnp:
        coords = [jax.lax.broadcasted_iota(jnp.int32, m.out_shape, d)
                  for d in range(nd_out)]
    else:  # broadcastable per-axis coordinates, expanded by the arithmetic
        coords = list(np.indices(m.out_shape, dtype=np.int32, sparse=True))
    # mixed-radix digit expansion (quotient in place, remainders appended)
    digits = list(coords)
    for sp in m.splits:
        q = digits[sp.axis] // sp.radix
        r = digits[sp.axis] % sp.radix
        digits[sp.axis] = q
        digits.append(r)
    # affine rows -> input coordinates (exact floor with common denominator)
    in_coords = []
    valid = xp.ones(m.out_shape, dtype=bool)
    for row, off in zip(m.affine.A, m.affine.b):
        nums, offn, L = _row_int_form(row, off)
        acc = xp.full(m.out_shape, offn, dtype=xp.int32)
        for n, d in zip(nums, digits):
            if n != 0:
                acc = acc + n * d
        c = acc if L == 1 else xp.floor_divide(acc, L)
        in_coords.append(c)
    for c, s in zip(in_coords, m.in_shape):
        valid = valid & (c >= 0) & (c < s)
    for d, bound in m.digit_bounds:
        valid = valid & (digits[d] < bound)
    strides = row_major_strides(m.in_shape)
    flat = xp.zeros(m.out_shape, dtype=xp.int32)
    for c, s, st in zip(in_coords, m.in_shape, strides):
        flat = flat + xp.clip(c, 0, s - 1) * st
    return flat, valid


@partial(jax.jit, static_argnums=(0,), static_argnames=("batch_dims",))
def apply_map(m: MixedRadixMap, x: jnp.ndarray, *, batch_dims: int = 0) -> jnp.ndarray:
    """Execute a gather map.  Leading ``batch_dims`` axes pass through."""
    assert x.shape[batch_dims:] == m.in_shape, (x.shape, m.in_shape, batch_dims)
    flat, valid = gather_indices(m)
    xf = x.reshape(x.shape[:batch_dims] + (-1,))
    out = jnp.take(xf, flat.reshape(-1), axis=batch_dims)
    out = out.reshape(x.shape[:batch_dims] + m.out_shape)
    if m.oob_possible:
        fill = jnp.asarray(m.fill, dtype=x.dtype)
        out = jnp.where(valid, out, fill)
    return out


def route_gather(maps, xs, *, batch_dims: int = 0,
                 overlay: bool = False) -> jnp.ndarray:
    """Multi-band gather (paper Route): each map reads its source into its
    band of the output; disjoint supports sum to the concat.  The canonical
    band loop, shared by the executor's COARSE multi-map path and
    :func:`repro.core.tm_ops.route`.

    ``overlay=True`` switches the combine from sum to *last-writer-wins*:
    each later band overwrites the output wherever its map is in-bounds.
    Bands may then overlap — the semantics of ``dynamic_update_slice``
    (base tensor + update window) rather than concatenate, and the floating
    point result is bit-exact because values are selected, never added."""
    out = None
    for x, m in zip(xs, maps):
        band = apply_map(m, x, batch_dims=batch_dims)
        if out is None:
            out = band
        elif overlay:
            _, valid = gather_indices(m)  # broadcasts over leading batch dims
            out = jnp.where(valid, band, out)
        else:
            out = out + band
    return out


def scatter_accumulate(m: MixedRadixMap, x: jnp.ndarray, out: jnp.ndarray,
                       *, batch_dims: int = 0) -> jnp.ndarray:
    """Scatter-add ``x`` (shaped ``m.out_shape``) into ``out`` via the map's
    *input* coordinates — used for Route (each band map writes its band) and
    for testing the paper's scatter formulation against the gather form."""
    flat, valid = gather_indices(m)
    outf = out.reshape(out.shape[:batch_dims] + (-1,))
    contrib = jnp.where(valid, x, jnp.zeros_like(x)) if m.oob_possible else x

    def upd(of, xb, fl, va):
        vals = jnp.where(va.reshape(-1), xb.reshape(-1), of[fl.reshape(-1)])
        return of.at[fl.reshape(-1)].set(vals)

    if batch_dims:
        for _ in range(batch_dims):
            upd = jax.vmap(upd, in_axes=(0, 0, None, None))
    res = upd(outf, contrib, flat, valid)
    return res.reshape(out.shape)
