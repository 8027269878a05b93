"""Jit'd wrappers for the img2col / conv kernels + dispatch registration."""

from __future__ import annotations

from functools import partial

import jax

from repro.core.dispatch import Decline, register_rule
from repro.core.instr import TMOpcode
from repro.kernels.img2col.img2col import conv2d, img2col
from repro.platform import pallas_interpret


@partial(jax.jit, static_argnames=("kh", "kw", "stride", "pad", "interpret"))
def img2col_call(x, *, kh, kw, stride=1, pad=0, interpret=None):
    return img2col(x, kh, kw, stride, pad, interpret=interpret)


@partial(jax.jit, static_argnames=("stride", "pad", "interpret"))
def conv2d_call(x, w, *, stride=1, pad=0, interpret=None):
    return conv2d(x, w, stride, pad, interpret=interpret)


# ---------------------------------------------------------------------------
# dispatch-registry rule: COARSE instructions tagged with img2col metadata
# run the slab kernel (on-chip patch assembly) instead of the generic gather.
# ---------------------------------------------------------------------------

def _img2col_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.COARSE or ins.ew is not None:
        return None
    cfg = (ins.meta or {}).get("img2col")
    if cfg is None or batch_dims != 0 or len(srcs) != 1:
        return None
    if srcs[0].ndim != 3 or ins.map_ is None \
            or srcs[0].shape != ins.map_.in_shape:
        return None
    # the map is ground truth, meta only a lowering hint: decline unless the
    # hint reconstructs the map exactly (the generic gather then runs map_)
    from repro.core.affine import img2col_map
    expect = img2col_map(ins.map_.in_shape, cfg["kh"], cfg["kw"],
                         cfg.get("stride", 1), cfg.get("pad", 0),
                         fill=ins.map_.fill)
    if expect != ins.map_:
        return None
    if not pallas_interpret(srcs[0]):
        # the in-VMEM patch assembly reshapes (oh·OW, kh·kw·C) tiles, a
        # shape cast Mosaic refuses; the generic row gather takes the map
        return Decline("patch assembly needs a shape cast Mosaic refuses")
    return "pallas.img2col"


def _img2col_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    cfg = ins.meta["img2col"]
    return img2col_call(srcs[0], kh=cfg["kh"], kw=cfg["kw"],
                        stride=cfg.get("stride", 1), pad=cfg.get("pad", 0),
                        interpret=interpret)


register_rule("img2col", _img2col_matches, _img2col_run, priority=20)
