"""Jit'd wrapper for the bilinear-resize kernel + dispatch registration."""

from functools import partial

import jax

from repro.core.dispatch import Decline, register_rule
from repro.core.instr import TMOpcode
from repro.kernels.resize.resize import resize_bilinear
from repro.platform import pallas_interpret


@partial(jax.jit, static_argnames=("out_h", "out_w", "interpret"))
def resize_call(x, *, out_h, out_w, interpret=None):
    return resize_bilinear(x, out_h, out_w, interpret=interpret)


# ---------------------------------------------------------------------------
# dispatch-registry rule: RESIZE instructions (meta carries out_h/out_w)
# ---------------------------------------------------------------------------

def _resize_matches(ins, srcs, batch_dims, segment_bytes=None):
    if ins.opcode != TMOpcode.RESIZE or batch_dims != 0:
        return None
    if len(srcs) != 1 or srcs[0].ndim != 3:
        return None
    if not pallas_interpret(srcs[0]):
        return Decline("rank-1 tap-table blocks break the TPU block-shape "
                       "rule")
    return "pallas.resize"


def _resize_run(ins, srcs, batch_dims, interpret, segment_bytes=None):
    return resize_call(srcs[0], out_h=ins.meta["out_h"],
                       out_w=ins.meta["out_w"], interpret=interpret)


register_rule("resize", _resize_matches, _resize_run, priority=20)
