"""Jit'd wrappers for the RME compaction kernels + dispatch registration."""

import math
from functools import lru_cache, partial

import jax
import numpy as np

from repro.core.dispatch import register_chain_rule, register_rule
from repro.core.instr import TMOpcode
from repro.kernels.rme_gather.rme_gather import (assemble, assemble_batched,
                                                 evaluate, evaluate_batched)


@partial(jax.jit, static_argnames=("capacity", "cmp", "score_index", "interpret"))
def evaluate_call(x, threshold, *, capacity, cmp="ge", score_index=0,
                  interpret=None):
    return evaluate(x, threshold, capacity, cmp=cmp, score_index=score_index,
                    interpret=interpret)


@partial(jax.jit, static_argnames=("capacity", "interpret"))
def assemble_call(x, mask, *, capacity, interpret=None):
    return assemble(x, mask, capacity, interpret=interpret)


@partial(jax.jit, static_argnames=("capacity", "cmp", "score_index", "interpret"))
def evaluate_batched_call(x, threshold, *, capacity, cmp="ge", score_index=0,
                          interpret=None):
    """(…, N, D) record streams: leading axes flatten onto the kernel grid."""
    batch = x.shape[:-2]
    rows, idx, cnt = evaluate_batched(
        x.reshape((-1,) + x.shape[-2:]), threshold, capacity, cmp=cmp,
        score_index=score_index, interpret=interpret)
    return (rows.reshape(batch + rows.shape[1:]),
            idx.reshape(batch + idx.shape[1:]),
            cnt.reshape(batch))


@partial(jax.jit, static_argnames=("capacity", "interpret"))
def assemble_batched_call(x, mask, *, capacity, interpret=None):
    batch = x.shape[:-2]
    packed, cnt = assemble_batched(
        x.reshape((-1,) + x.shape[-2:]), mask.reshape((-1,) + mask.shape[-1:]),
        capacity, interpret=interpret)
    return packed.reshape(batch + packed.shape[1:]), cnt.reshape(batch)


# ---------------------------------------------------------------------------
# dispatch-registry rules: FINE instructions whose RME config the sort-based
# compaction kernel supports (runtime predicate/mask, static capacity, record
# streams with any number of leading batch axes — the batched kernels lift
# the compaction grid over them).  Static lane masks and top-k fall back.
# ---------------------------------------------------------------------------

def _evaluate_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.FINE_EVALUATE:
        return None
    cfg = ins.rme
    if cfg.top_k is not None or cfg.capacity is None or cfg.threshold is None:
        return None
    if len(srcs) != 1 or srcs[0].ndim != batch_dims + 2:
        return None
    return "pallas.rme.evaluate"


def _evaluate_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    if batch_dims == 0:
        rows, _, _ = evaluate_call(srcs[0], ins.rme.threshold,
                                   capacity=ins.rme.capacity, cmp=ins.rme.cmp,
                                   score_index=ins.rme.score_index,
                                   interpret=interpret)
        return rows
    rows, _, _ = evaluate_batched_call(
        srcs[0], ins.rme.threshold, capacity=ins.rme.capacity,
        cmp=ins.rme.cmp, score_index=ins.rme.score_index, interpret=interpret)
    return rows


def _assemble_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.FINE_ASSEMBLE:
        return None
    cfg = ins.rme
    if cfg.lane_mask is not None or cfg.capacity is None:
        return None
    if len(srcs) != 2 or srcs[0].ndim != batch_dims + 2 \
            or srcs[1].ndim != batch_dims + 1:
        return None
    if srcs[0].shape[:-1] != srcs[1].shape:
        return None
    return "pallas.rme.assemble"


def _assemble_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    if batch_dims == 0:
        packed, _ = assemble_call(srcs[0], srcs[1],
                                  capacity=ins.rme.capacity,
                                  interpret=interpret)
        return packed
    packed, _ = assemble_batched_call(srcs[0], srcs[1],
                                      capacity=ins.rme.capacity,
                                      interpret=interpret)
    return packed


def _rme_segments(ins, srcs, batch_dims, segment_bytes=None):
    # one grid step per record stream (the batched kernels' grid)
    return max(1, math.prod(srcs[0].shape[:batch_dims]))


# ---------------------------------------------------------------------------
# chain rule: coarse pre-links that only re-lay the record stream out (a
# reshape of the raw head grid into records) cost nothing in the evaluate
# kernel's load — the stream is the producer's buffer viewed as records, and
# the tail is ONE launch whose record stream never materializes
# ---------------------------------------------------------------------------

def _chain_eval_maps(instrs, srcs, batch_dims):
    """Lifted pre-link maps + the FINE link's stream rank, or (None, 0)."""
    from repro.core.affine import batch_extend_map
    last = instrs[-1]
    if last.opcode != TMOpcode.FINE_EVALUATE:
        return None, 0
    cfg = last.rme
    if cfg.top_k is not None or cfg.capacity is None or cfg.threshold is None:
        return None, 0
    if len(last.srcs) != 1 or srcs[-1][0] is not None:
        return None, 0
    x = srcs[0][0]
    if x is None:
        return None, 0
    batch = x.shape[:batch_dims]
    maps = []
    for k, ins in enumerate(instrs[:-1]):
        if ins.opcode != TMOpcode.COARSE or ins.map_ is None \
                or ins.ew is not None or len(ins.srcs) != 1:
            return None, 0
        if k > 0 and srcs[k][0] is not None:
            return None, 0
        m = batch_extend_map(ins.map_, batch)
        if k == 0 and x.shape != m.in_shape:
            return None, 0
        if maps and m.in_shape != maps[-1].out_shape:
            return None, 0
        maps.append(m)
    fine_bd = batch_dims + (last.meta or {}).get("batch_dims", 0)
    if len(maps[-1].out_shape) != fine_bd + 2:
        return None, 0
    return tuple(maps), fine_bd


@lru_cache(maxsize=256)
def _layout_identity(maps) -> bool:
    """True when the pre-links only re-lay the data out: the pullback reads
    every stream element from the same flat position, nothing out of
    bounds (a permanent property — cached, so repeat runs stay cheap)."""
    from repro.kernels.tm_affine.chain import fold_pullback
    try:
        J, OK, _ = fold_pullback(maps)
    except ValueError:
        return False
    return OK is None and bool((J == np.arange(J.size)).all())


def _chain_eval_lower(instrs, srcs, batch_dims, interpret,
                      segment_bytes=None):
    """Single-pass chained-evaluate lowering, or None."""
    maps, _ = _chain_eval_maps(instrs, srcs, batch_dims)
    if maps is None or not _layout_identity(maps):
        return None
    x = srcs[0][0]
    cfg = instrs[-1].rme
    stream = maps[-1].out_shape
    rows, _, _ = evaluate_batched_call(
        x.reshape(stream), cfg.threshold, capacity=cfg.capacity,
        cmp=cfg.cmp, score_index=cfg.score_index, interpret=interpret)
    return rows, "pallas.chain+rme.evaluate", max(1, math.prod(stream[:-2]))


register_rule("rme_gather.evaluate", _evaluate_matches, _evaluate_run,
              priority=10, segments=_rme_segments)
register_rule("rme_gather.assemble", _assemble_matches, _assemble_run,
              priority=10, segments=_rme_segments)
register_chain_rule("rme_gather.chain_evaluate", _chain_eval_lower,
                    priority=10)
