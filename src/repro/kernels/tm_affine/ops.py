"""Jit'd public wrappers for the generic TM kernel + dispatch registration."""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.core.affine import MixedRadixMap, batch_extend_map
from repro.core.dispatch import Decline, register_chain_rule, register_rule
from repro.core.engine import EW_FNS
from repro.core.instr import TMOpcode
from repro.core.schedule import map_segments
from repro.kernels.tm_affine import chain as ch
from repro.kernels.tm_affine.chain import ChainSig, chain_plan_of, tm_chain
from repro.kernels.tm_affine.tm_affine import (analyze_block_mode,
                                               gather_sig, tm_affine)
from repro.platform import pallas_interpret


@partial(jax.jit, static_argnums=(1,),
         static_argnames=("interpret", "force_mode", "segment_bytes"))
def tm_affine_call(x: jnp.ndarray, m: MixedRadixMap, *,
                   interpret: bool | None = None,
                   force_mode: str | None = None,
                   segment_bytes: int | None = None) -> jnp.ndarray:
    return tm_affine(x, m, interpret=interpret, force_mode=force_mode,
                     segment_bytes=segment_bytes)


def _on_tpu(*arrays) -> bool:
    return not pallas_interpret(*arrays)


def plan_of(m: MixedRadixMap):
    """Expose the decode step (block plan or None) for tests/benchmarks."""
    return analyze_block_mode(m)


# ---------------------------------------------------------------------------
# dispatch-registry rules: the generic coarse-grained datapath
# ---------------------------------------------------------------------------

# MixedRadixMap is frozen/hashable: memoize the batch lift and the decode
# analysis so match + run share one computation per (map, batch, budget)
_lift_cached = lru_cache(maxsize=512)(batch_extend_map)


def _lifted(ins, srcs, batch_dims) -> MixedRadixMap | None:
    if ins.map_ is None:
        return None
    batch = srcs[0].shape[:batch_dims]
    if srcs[0].shape[batch_dims:] != ins.map_.in_shape:
        return None
    return _lift_cached(ins.map_, batch)


def _coarse_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.COARSE:
        return None
    m = _lifted(ins, srcs, batch_dims)
    if m is None:
        return None
    mode = ("block" if analyze_block_mode(m, None, segment_bytes) is not None
            else "gather")
    if ins.ew is not None:
        # the kernel epilogue streams y in output layout — broadcastable
        # operands are the engine's job, decline and fall back
        if len(srcs) != 2 or srcs[1].shape != m.out_shape:
            return None
    elif len(srcs) != 1:
        return None
    if mode == "gather" and _on_tpu(srcs[0]):
        why = ch.tpu_decline(gather_sig(
            m, srcs[0].dtype, ins.ew.value if ins.ew is not None else None,
            segment_bytes))
        if why is not None:
            return Decline(why)
    return f"pallas.{mode}+ew" if ins.ew is not None else f"pallas.{mode}"


def _coarse_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    # unjitted entry: the kernels jit themselves, and the row gathers' address
    # tables must reach them as operands, not as constants of an outer jit
    m = _lifted(ins, srcs, batch_dims)
    if ins.ew is not None:
        return tm_affine(srcs[0], m, interpret=interpret, y=srcs[1],
                         ew=ins.ew.value, segment_bytes=segment_bytes)
    return tm_affine(srcs[0], m, interpret=interpret,
                     segment_bytes=segment_bytes)


def _coarse_segments(ins, srcs, batch_dims, segment_bytes=None):
    # the map is already batch-lifted, so this is exactly the grid the
    # kernel launches — and exactly schedule's shared count (one source)
    return map_segments(_lifted(ins, srcs, batch_dims),
                        segment_bytes=segment_bytes)


def _route_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.COARSE or ins.maps is None:
        return None
    if ins.meta and ins.meta.get("overlay"):
        return None  # overwrite semantics: the overlay rule's
    n_band = len(ins.maps)
    expected = n_band + (1 if ins.ew is not None else 0)
    if len(srcs) != expected:
        return None
    for x, m in zip(srcs, ins.maps):
        if x.shape[batch_dims:] != m.in_shape:
            return None
    if _on_tpu(srcs[0]):
        batch = srcs[0].shape[:batch_dims]
        for x, m in zip(srcs, ins.maps):
            lifted = _lift_cached(m, batch)
            if analyze_block_mode(lifted, None, segment_bytes) is None:
                why = ch.tpu_decline(gather_sig(lifted, x.dtype, None,
                                                segment_bytes))
                if why is not None:
                    return Decline(why)
    return "pallas.route+ew" if ins.ew is not None else "pallas.route"


def _route_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    # band loop (Branch stage): one kernel launch per band, disjoint supports
    batch = srcs[0].shape[:batch_dims]
    out = None
    for x, m in zip(srcs, ins.maps):
        band = tm_affine(x, _lift_cached(m, batch), interpret=interpret,
                         segment_bytes=segment_bytes)
        out = band if out is None else out + band
    if ins.ew is not None:
        out = EW_FNS[ins.ew.value](out, srcs[-1])
    return out


def _overlay_sig(ins, srcs, batch_dims, segment_bytes):
    batch = srcs[0].shape[:batch_dims]
    return ChainSig(links=(), route_maps=tuple(_lift_cached(m, batch)
                                               for m in ins.maps),
                    route_band=0, dtype=str(srcs[0].dtype),
                    segment_bytes=segment_bytes, overlay=True)


def _overlay_matches(ins, srcs, batch_dims, segment_bytes=None):
    """Overlay Route (``dynamic_update_slice``): later bands overwrite the
    base band wherever their map is in bounds — one launch, all bands."""
    if ins.opcode != TMOpcode.COARSE or ins.maps is None \
            or not (ins.meta and ins.meta.get("overlay")) \
            or ins.ew is not None or len(srcs) != len(ins.maps):
        return None
    if any(x.shape[batch_dims:] != m.in_shape or x.dtype != srcs[0].dtype
           for x, m in zip(srcs, ins.maps)):
        return None
    if _on_tpu(srcs[0]):
        why = ch.tpu_decline(_overlay_sig(ins, srcs, batch_dims,
                                          segment_bytes))
        if why is not None:
            return Decline(why)
    return "pallas.overlay"


def _overlay_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    sig = _overlay_sig(ins, srcs, batch_dims, segment_bytes)
    return tm_chain(sig, srcs[0], tuple(srcs[1:]), interpret=interpret,
                    name="tm_overlay")


def _overlay_segments(ins, srcs, batch_dims, segment_bytes=None):
    return chain_plan_of(_overlay_sig(ins, srcs, batch_dims,
                                      segment_bytes)).n_segments


def _route_segments(ins, srcs, batch_dims, segment_bytes=None):
    batch = srcs[0].shape[:batch_dims]
    return sum(map_segments(_lift_cached(m, batch),
                            segment_bytes=segment_bytes) for m in ins.maps)


# ---------------------------------------------------------------------------
# chain rule: a forwarding chain of coarse instructions as ONE megakernel
# (kernels/tm_affine/chain.py) — intermediates stream through VMEM scratch
# ---------------------------------------------------------------------------

def _chain_sig_build(instrs, srcs, batch_dims, segment_bytes):
    """Build ``(ChainSig, operand slabs)``, or ``(None, None)`` when this
    rule cannot take the chain.

    Legal chains: every link COARSE; links 1..k-1 single-map with the
    streamed buffer as their data source (``srcs[k][0] is None``); the last
    link may instead be a multi-band Route whose chain band is the streamed
    buffer.  Epilogue operands must already be in the link's (lifted) output
    layout — the same contract as the per-instruction rule.
    """
    x = srcs[0][0]
    if x is None or instrs[0].opcode != TMOpcode.COARSE:
        return None, None
    batch = x.shape[:batch_dims]
    dtype = x.dtype
    links = []
    route_maps = None
    route_band = 0
    prev_out = None
    slabs = []
    n = len(instrs)
    for k, ins in enumerate(instrs):
        if ins.opcode != TMOpcode.COARSE:
            return None, None
        cur_srcs = srcs[k]
        if ins.maps is not None:
            # multi-band Route — only as the terminal link, without epilogue;
            # overlay Routes (overwrite semantics) never chain: the chain
            # kernel sums bands
            if k != n - 1 or ins.ew is not None \
                    or (ins.meta and ins.meta.get("overlay")):
                return None, None
            if len(cur_srcs) != len(ins.maps):
                return None, None
            band = [i for i, s in enumerate(cur_srcs) if s is None]
            if k == 0 or len(band) != 1:
                return None, None
            route_band = band[0]
            route_maps = []
            for i, (s, m) in enumerate(zip(cur_srcs, ins.maps)):
                lifted = _lift_cached(m, batch)
                if i == route_band:
                    if lifted.in_shape != prev_out:
                        return None, None
                else:
                    if s is None or s.shape != lifted.in_shape \
                            or s.dtype != dtype:
                        return None, None
                    slabs.append(s)
                route_maps.append(lifted)
            route_maps = tuple(route_maps)
            break
        if ins.map_ is None:
            return None, None
        m = _lift_cached(ins.map_, batch)
        if k == 0:
            if x.shape != m.in_shape:
                return None, None
        else:
            if cur_srcs[0] is not None or m.in_shape != prev_out:
                return None, None
        ew = None
        if ins.ew is not None:
            if len(cur_srcs) != 2:
                return None, None
            y = cur_srcs[1]
            if y is None or y.shape != m.out_shape or y.dtype != dtype:
                return None, None
            ew = ins.ew.value
            slabs.append(y)
        elif len(cur_srcs) != 1:
            return None, None
        links.append((m, ew))
        prev_out = m.out_shape
    sig = ChainSig(links=tuple(links), route_maps=route_maps,
                   route_band=route_band, dtype=str(dtype),
                   segment_bytes=segment_bytes)
    return sig, tuple(slabs)


def _chain_lower(instrs, srcs, batch_dims, interpret, segment_bytes=None):
    """Single-pass chain lowering: legality + build + run, or None."""
    sig, slabs = _chain_sig_build(instrs, srcs, batch_dims, segment_bytes)
    if sig is None:
        return None
    why = ch.tpu_decline(sig) if _on_tpu(srcs[0][0]) else None
    if why is not None:
        return Decline(why)  # the per-instruction kernels take the links
    val = tm_chain(sig, srcs[0][0], slabs, interpret=interpret)
    path = ("pallas.chain+route" if sig.route_maps is not None
            else "pallas.chain")
    return val, path, chain_plan_of(sig).n_segments


register_rule("tm_affine.route", _route_matches, _route_run, priority=10,
              segments=_route_segments,
              launches=lambda ins, srcs, batch_dims: len(ins.maps))
register_rule("tm_affine.overlay", _overlay_matches, _overlay_run,
              priority=10, segments=_overlay_segments)
register_rule("tm_affine", _coarse_matches, _coarse_run, priority=0,
              segments=_coarse_segments)
register_chain_rule("tm_affine.chain", _chain_lower, priority=0)
