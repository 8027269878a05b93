"""Cross-engine megakernels — a TM chain streamed through a compute kernel.

The hand-rolled epilogues in :mod:`repro.kernels.matmul_tm.matmul_tm`
(transpose, pixel-shuffle, split) prove the paper's Fig. 5c forwarding at
the engine boundary for three fixed manipulations.  This module generalizes
them to ANY legal chain the row programs of
:mod:`repro.kernels.tm_affine.chain` can express, in both directions:

* **compute→TM** (``pallas.xchain.commit``): at grid step 0 the eqn
  (dot_general / conv) computes into a VMEM slab laid out as the chain
  source's rows; every grid step then assembles one output segment from
  that slab through the chain's row program (masks, epilogue operands,
  route bands), committing final segments to HBM.  The eqn's result never
  materializes as a tensor.
* **TM→compute** (``pallas.xchain.prologue``): the chain's grid steps
  assemble output segments into a VMEM slab — the consumer's input, staged
  in-launch — and the last step binds the eqn with that slab as the
  crossing operand.  The chain's output never materializes.

Both are ONE ``pallas_call``.  Anything the signature builder cannot take
(non-coarse links, mixed fills, scalar operands) declines with ``None`` and
the caller runs the split path, bit-exact — the same decline contract as the
TM-internal chain rule.  On a TPU the compute stage must also have a Mosaic
lowering (a canonical 2-D ``dot_general`` whose crossing matrix the slab's
rows tile in whole 128-lane pieces) and fit VMEM; otherwise the rule
returns a :class:`~repro.core.dispatch.Decline` before any launch.

Bit-exactness of the compute stage: re-binding the eqn's primitive inside
an interpret-mode kernel dispatches the same XLA computation eager would.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.dispatch import Decline, register_xengine_rule
from repro.core.fusion import XENGINE_PRIMS
from repro.kernels.tm_affine import rows as rw
from repro.kernels.tm_affine.chain import build_chain_plan

_EXECUTABLES: dict = {}


def _bind_eqn(eqn, invals):
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    return eqn.primitive.bind(*subfuns, *invals, **bind_params)


def _stage_fn(eqn, interpret: bool):
    """The eqn as a kernel compute stage.  Mosaic's matmul accumulates in
    32 bits: a narrower dot result is accumulated there and rounded once,
    which is what XLA's own TPU dot does."""
    out = jnp.dtype(eqn.outvars[0].aval.dtype)
    if interpret or eqn.primitive.name != "dot_general" or out.itemsize >= 4:
        return lambda *ops: _bind_eqn(eqn, ops)
    acc = jnp.int32 if jnp.issubdtype(out, jnp.integer) else jnp.float32
    params = dict(eqn.params, preferred_element_type=acc)

    def dot(*ops):
        return jax.lax.dot_general_p.bind(*ops, **params).astype(out)
    return dot


def _eqn_key(eqn) -> tuple:
    """Hashable identity of an eqn's computation (primitive + params):
    executables built for one eqn are reused for any eqn with the same key
    and operand shapes/dtypes."""
    return (eqn.primitive.name,
            tuple(sorted((k, repr(v)) for k, v in eqn.params.items())))


def _canonical_dot(eqn, shapes) -> bool:
    """A 2-D ``(M, K) @ (K, N)`` dot — the compute stage Mosaic lowers."""
    if eqn.primitive.name != "dot_general" or len(shapes) != 2:
        return False
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    return (not lb and not rb and tuple(lc) == (1,) and tuple(rc) == (0,)
            and all(len(s) == 2 for s in shapes))


def _tpu_decline(direction, eqn, op_sds, prog: rw.RowProgram,
                 cross_shape) -> str | None:
    """Why the crossing cannot launch on a TPU (None: it can)."""
    if not _canonical_dot(eqn, [s for s, _ in op_sds]):
        return (f"compute stage {eqn.primitive.name} is not a 2-D "
                f"dot_general, which is all Mosaic lowers here")
    # the slab's row view must be the dot's matrix with its lane axis cut
    # into whole 128-lane rows (a reshape Mosaic performs), or the matrix
    lanes = (prog.gathers[0].l_in if direction == "compute_to_tm"
             else prog.L)
    width = cross_shape[-1]
    if lanes != width and (width % lanes or lanes % 128):
        return (f"slab rows of {lanes} lanes do not tile the dot's "
                f"{tuple(cross_shape)} layout")
    staged = math.prod(cross_shape) // cross_shape[-1] \
        * rw.row_bytes(cross_shape[-1])
    ops = sum(2 * math.prod(s) * jnp.dtype(d).itemsize for s, d in op_sds)
    need = rw.vmem_bytes(prog) + staged + ops
    if need > rw.VMEM_BUDGET:
        return f"crossing needs {need} B of VMEM (budget {rw.VMEM_BUDGET})"
    return None


def _commit_executable(sig, eqn, op_sds: tuple, interpret: bool):
    """(jitted callable(*eqn_ops, *slabs) -> chain output, plan, segments)
    for a compute→TM crossing."""
    key = ("commit", sig, _eqn_key(eqn), op_sds, interpret)
    hit = _EXECUTABLES.get(key)
    if hit is None:
        plan = build_chain_plan(sig)
        head = rw.Head(fn=_stage_fn(eqn, interpret), n_ops=len(op_sds))
        call = _with_tables(rw.build_call(plan.program, interpret, head=head,
                                          name="xchain"),
                            rw.address_tables(plan.program, head=True))
        hit = _EXECUTABLES[key] = (call, plan, plan.n_segments)
    return hit


def _prologue_executable(sig, eqn, op_sds: tuple, cross_pos: int,
                         interpret: bool):
    """(jitted callable(chain_src, *slabs, *other_ops) -> eqn output, plan,
    segments) for a TM→compute crossing."""
    key = ("prologue", sig, _eqn_key(eqn), op_sds, cross_pos, interpret)
    hit = _EXECUTABLES.get(key)
    if hit is None:
        plan = build_chain_plan(sig)
        out_aval = eqn.outvars[0].aval
        stage = _stage_fn(eqn, interpret)

        def compute(xv, *others):
            invals = list(others)
            invals.insert(cross_pos, xv)
            return stage(*invals)

        sink = rw.Sink(fn=compute, n_ops=len(op_sds) - 1,
                       out_shape=tuple(out_aval.shape),
                       out_dtype=out_aval.dtype)
        call = _with_tables(rw.build_call(plan.program, interpret, sink=sink,
                                          name="xchain"),
                            rw.address_tables(plan.program))
        hit = _EXECUTABLES[key] = (call, plan, plan.n_segments)
    return hit


def _with_tables(call, tabs):
    """Jit ``call`` and feed it the program's address tables as operands."""
    fn = jax.jit(call)
    tabs = tabs[:3] + (None,)
    return lambda *operands: fn(tabs, *operands)


# ---------------------------------------------------------------------------
# the registry rule
# ---------------------------------------------------------------------------

def _is_tensor(a) -> bool:
    return hasattr(a, "shape") and hasattr(a, "dtype") and \
        len(getattr(a, "shape", ())) >= 1


def _sds(arrays) -> tuple:
    # hot path: arrays are jnp arrays / ShapeDtypeStructs, both carry .dtype
    # — no asarray materialization for a cache key
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)


def _xengine_lower(direction, eqn_node, eqn_srcs, instrs, tm_srcs,
                   interpret, segment_bytes=None):
    """Single-pass cross-engine lowering: legality + build + run, or None."""
    from repro.kernels.tm_affine.ops import _chain_sig_build

    eqn = eqn_node.eqn
    if eqn.primitive.name not in XENGINE_PRIMS:
        return None
    if len(eqn_node.dst_names) != 1 or eqn.primitive.multiple_results:
        return None

    if direction == "compute_to_tm":
        if any(not _is_tensor(a) for a in eqn_srcs):
            return None
        y_aval = eqn.outvars[0].aval
        stand_in = jax.ShapeDtypeStruct(tuple(y_aval.shape), y_aval.dtype)
        srcs = [list(s) for s in tm_srcs]
        if not srcs or srcs[0][0] is not None:
            return None
        srcs[0][0] = stand_in
        sig, slabs = _chain_sig_build(instrs, srcs, 0, segment_bytes)
        if sig is None:
            return None
        op_sds = _sds(eqn_srcs)
        if not interpret:
            why = _tpu_decline(direction, eqn, op_sds,
                               build_chain_plan(sig).program, stand_in.shape)
            if why is not None:
                return Decline(why)
        fn, plan, segs = _commit_executable(sig, eqn, op_sds, interpret)
        return fn(*eqn_srcs, *slabs), "pallas.xchain.commit", segs

    if direction == "tm_to_compute":
        cross = [i for i, a in enumerate(eqn_srcs) if a is None]
        if len(cross) != 1:
            return None
        cross_pos = cross[0]
        others = [a for i, a in enumerate(eqn_srcs) if i != cross_pos]
        if any(not _is_tensor(a) for a in others):
            return None
        if tm_srcs and (not tm_srcs[0] or tm_srcs[0][0] is None):
            return None
        sig, slabs = _chain_sig_build(instrs, tm_srcs, 0, segment_bytes)
        if sig is None:
            return None
        a_aval = eqn.invars[cross_pos].aval
        if tuple(a_aval.shape) != tuple(sig.out_shape) \
                or jnp.dtype(a_aval.dtype) != jnp.dtype(sig.dtype):
            return None
        x = tm_srcs[0][0]
        op_sds = _sds([x if i == cross_pos else eqn_srcs[i]
                       for i in range(len(eqn_srcs))])
        # the crossing slot's shape/dtype in the cache key comes from the
        # chain output, which IS the operand the eqn consumes
        op_sds = tuple(
            ((tuple(sig.out_shape), str(jnp.dtype(sig.dtype)))
             if i == cross_pos else op_sds[i])
            for i in range(len(op_sds)))
        if not interpret:
            why = _tpu_decline(direction, eqn, op_sds,
                               build_chain_plan(sig).program, sig.out_shape)
            if why is not None:
                return Decline(why)
        fn, plan, segs = _prologue_executable(sig, eqn, op_sds, cross_pos,
                                              interpret)
        return fn(x, *slabs, *others), "pallas.xchain.prologue", segs

    return None


register_xengine_rule("matmul_tm.xchain", _xengine_lower, priority=0)
