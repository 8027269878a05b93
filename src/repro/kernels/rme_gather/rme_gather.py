"""RME compaction Pallas kernel — assemble/evaluate on TPU.

The masking crossbar of the paper's RME streams records past a predicate and
commits the survivors, in order, to a packed buffer.  The kernel does
exactly that: one grid step per record stream, a scalar loop over the
stream's records (the predicate inputs sit in SMEM), and each survivor's
record row copied to the next free slot of the commit buffer while it has
room.  The result is a statically shaped packed block plus a survivor count
— Bboxcal (paper Fig. 2c) end to end, and the same configuration drives MoE
token dispatch.

Records are laid out as ``(rows, 1, D)``: the leading axis is untiled, so a
record is addressed by a dynamic scalar index, which is what Mosaic lowers
(it has no sort and no flat gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.platform import pallas_interpret

_CMP = {"ge": lambda s, t: s >= t, "gt": lambda s, t: s > t,
        "le": lambda s, t: s <= t, "lt": lambda s, t: s < t}


def _compact_kernel(*refs, n: int, capacity: int, keep):
    """Pack the records whose ``keep(i)`` holds, in order, up to capacity."""
    *pred_refs, x_ref, o_ref, idx_ref, cnt_ref = refs
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def fill_idx(j, carry):
        idx_ref[0, 0, j] = n
        return carry

    jax.lax.fori_loop(0, capacity, fill_idx, 0)

    def body(i, cnt):
        take = keep(pred_refs, i) & (cnt < capacity)

        @pl.when(take)
        def _commit():
            o_ref[cnt] = x_ref[i]
            idx_ref[0, 0, cnt] = i

        return cnt + take.astype(jnp.int32)

    cnt_ref[0, 0, 0] = jax.lax.fori_loop(0, n, body, jnp.int32(0))


def _compact(x, pred_args, keep, capacity: int, interpret):
    """(B, N, D) records + per-stream SMEM predicate inputs -> packed
    (B, capacity, D), (B, capacity) source indices, (B, 1) counts."""
    B, N, D = x.shape
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    pred_specs = [smem((1, 1, N), lambda b: (b, 0, 0)) if a.ndim == 3
                  else smem(a.shape, lambda b: (0,)) for a in pred_args]
    kern = functools.partial(_compact_kernel, n=N, capacity=capacity,
                             keep=keep)
    rows, idx, cnt = pl.pallas_call(
        kern,
        grid=(B,),
        in_specs=pred_specs + [pl.BlockSpec((N, 1, D), lambda b: (b, 0, 0))],
        out_specs=[pl.BlockSpec((capacity, 1, D), lambda b: (b, 0, 0)),
                   smem((1, 1, capacity), lambda b: (b, 0, 0)),
                   smem((1, 1, 1), lambda b: (b, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * capacity, 1, D), x.dtype),
                   jax.ShapeDtypeStruct((B, 1, capacity), jnp.int32),
                   jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)],
        name="rme_gather",
        interpret=(pallas_interpret(x) if interpret is None
                   else interpret),
    )(*pred_args, x.reshape(B * N, 1, D))
    return (rows.reshape(B, capacity, D), idx.reshape(B, capacity),
            cnt.reshape(B, 1))


def evaluate_batched(x: jnp.ndarray, threshold, capacity: int, *,
                     cmp: str = "ge", score_index: int = 0,
                     interpret: bool | None = None):
    """Batched evaluate: (B, N, D) -> (B, capacity, D) + idx + counts.

    The compaction grid is lifted over the leading axis — one grid step per
    record stream.  Scores compare at the promoted dtype (the weak-typed
    python-float threshold: int records compare in float, not truncated);
    that compare is exact in float32, where the kernel evaluates it."""
    B, N, D = x.shape
    dt = jnp.result_type(x.dtype, threshold)
    scores = x[:, :, score_index].astype(dt).astype(jnp.float32)
    thr = jnp.asarray([threshold], dtype=dt).astype(jnp.float32)

    def keep(refs, i):
        s_ref, t_ref = refs
        return _CMP[cmp](s_ref[0, 0, i], t_ref[0])

    return _compact(x, [scores.reshape(B, 1, N), thr], keep, capacity,
                    interpret)


def evaluate(x: jnp.ndarray, threshold, capacity: int, *, cmp: str = "ge",
             score_index: int = 0, interpret: bool | None = None):
    """Threshold-filter rows of (N, D) -> packed (capacity, D) + idx + count."""
    rows, idx, cnt = evaluate_batched(x[None], threshold, capacity, cmp=cmp,
                                      score_index=score_index,
                                      interpret=interpret)
    return rows[0], idx[0], cnt[0]


def assemble_batched(x: jnp.ndarray, mask: jnp.ndarray, capacity: int, *,
                     interpret: bool | None = None):
    """Batched assemble: (B, N, D) + (B, N) mask -> (B, capacity, D) + counts."""
    B, N, D = x.shape

    def keep(refs, i):
        return refs[0][0, 0, i] != 0

    rows, _, cnt = _compact(x, [mask.astype(jnp.int32).reshape(B, 1, N)],
                            keep, capacity, interpret)
    return rows, cnt


def assemble(x: jnp.ndarray, mask: jnp.ndarray, capacity: int, *,
             interpret: bool | None = None):
    """Pack rows of (N, D) selected by a runtime mask -> (capacity, D) + count."""
    rows, cnt = assemble_batched(x[None], mask[None], capacity,
                                 interpret=interpret)
    return rows[0], cnt[0]
