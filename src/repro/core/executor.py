"""8-stage TM execution model (paper Fig. 3), as an interpreter.

The :class:`TMExecutor` runs a :class:`~repro.core.instr.TMProgram` over a
buffer file, mirroring the TMU FSM:

  Fetch/Decode  -> iterate the instruction list, dispatch on opcode
  Tensor Load   -> resolve ``srcs`` from the buffer dict (HBM analogue)
  Fine TM       -> RME assemble / evaluate
  Element-wise  -> vector add/sub/mul/max
  Coarse TM     -> the unified address engine (apply_map)
  Tensor Store  -> bind ``dst`` in the buffer dict
  Branch        -> implicit: apply_map/rme internally iterate segments;
                   at program level, multi-map ops (Route) loop over bands.

Backends:
  * ``reference`` — execute instructions one by one (every intermediate hits
    "HBM", like a CPU fallback / the paper's unfused baseline).
  * ``fused``     — run the fusion pass first (near-memory execution: elided
    intermediates never materialize), then execute.
  * ``pallas``    — lower each instruction through the kernel-dispatch
    registry (:mod:`repro.core.dispatch`) onto the hand-written Pallas
    kernels (compiled on a TPU, interpreted elsewhere); unsupported
    configurations fall back to the reference engine.
    ``last_lowering`` records which path each instruction took.

The reference/fused executors are jit-compatible: running them under
``jax.jit`` stages the whole program into one XLA computation, which is the
final TPU-native form (XLA then fuses the remaining gathers with neighbours).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import rme
from repro.core.dispatch import (Lowering, LoweringReport, lower_chain,
                                 lower_instr)
from repro.core.engine import EW_FNS, apply_map, route_gather
from repro.core.fusion import ForwardChain, FusionReport, forwarding_chains, fuse
from repro.core.instr import EwOp, TMInstr, TMOpcode, TMProgram
from repro.core.schedule import CycleParams

_EW: dict[EwOp, Callable] = {op: EW_FNS[op.value] for op in EwOp}

BACKENDS = ("reference", "fused", "pallas")


@dataclasses.dataclass
class TMExecutor:
    backend: str = "fused"  # "reference" | "fused" | "pallas"
    # custom cycle params re-segment the launched Pallas grids (the ping-pong
    # budget params.segment_bytes flows executor -> dispatch -> kernels); None
    # keeps the shared default, so model and kernels still agree
    params: CycleParams | None = None
    # pallas only: execute each forwarding chain (fusion.forwarding_chains)
    # as ONE segment-streaming Pallas kernel — intermediates hand off through
    # VMEM scratch instead of round-tripping HBM, and the chain's lowering
    # report shows a single record with launches=1 covering all its
    # instructions.  Chains the chain registry declines fall back to
    # per-instruction lowering, bit-exact either way.
    fuse_chains: bool = False
    # duck-typed repro.obs Tracer: per-instruction / per-chain spans on the
    # calling thread's track, recorded only at Tracer(detail="instr")
    # (None or the no-op tracer = tracing off; the hot path pays one
    # attribute check per instruction)
    tracer: object = None
    # pallas only: the degradation-ladder quarantine (a mutable set shared
    # with the owning compile-cache entry).  When set, a kernel rule that
    # raises is quarantined and the instruction falls through to the next
    # rule / the reference engine instead of failing the run — see
    # dispatch.lower_instr.  None keeps fail-fast semantics.
    quarantine: set | None = None
    last_report: FusionReport | None = None
    last_lowering: LoweringReport | None = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of {BACKENDS}")

    def __call__(self, prog: TMProgram, buffers: dict[str, jnp.ndarray],
                 *, batch_dims: int = 0) -> dict[str, jnp.ndarray]:
        out, lowering, fusion = self.run(prog, buffers, batch_dims=batch_dims)
        # convenience aliases for the *last* call — racy by construction
        # under concurrent callers; threaded code must use run() instead
        if fusion is not None:
            self.last_report = fusion
        self.last_lowering = lowering
        return out

    def run(self, prog: TMProgram, buffers: dict[str, jnp.ndarray],
            *, batch_dims: int = 0,
            ) -> tuple[dict[str, jnp.ndarray], LoweringReport,
                       FusionReport | None]:
        """Execute ``prog`` and return ``(outputs, lowering, fusion)``.

        Unlike :meth:`__call__` this mutates no executor state — per-call
        reports are returned, so one executor is safe to share across the
        serving runtime's worker threads."""
        fusion = None
        if self.backend == "fused":
            prog, fusion = fuse(prog)
        lowering = LoweringReport(backend=self.backend)
        bufs = dict(buffers)
        chain_at: dict[int, ForwardChain] = {}
        if self.backend == "pallas" and self.fuse_chains:
            chain_at = {c.instrs[0]: c for c in forwarding_chains(prog)}
        tr = self.tracer
        # instruction/chain spans only at Tracer(detail="instr") — at the
        # default "phase" detail a traced serving run stays lock-cheap
        traced = (tr is not None and tr.enabled
                  and getattr(tr, "detail", "phase") == "instr")
        i = 0
        while i < len(prog.instrs):  # Fetch
            chain = chain_at.get(i)
            if chain is not None:
                if traced:
                    with tr.span(f"chain/{prog.instrs[chain.instrs[-1]].dst}",
                                 instrs=len(chain.instrs)):
                        self._run_chain(chain, prog, bufs, batch_dims,
                                        lowering)
                else:
                    self._run_chain(chain, prog, bufs, batch_dims, lowering)
                i = chain.instrs[-1] + 1
                continue
            ins = prog.instrs[i]
            if traced:
                with tr.span(f"instr/{ins.opcode.value}/{ins.dst}"):
                    bufs[ins.dst] = self._dispatch(ins, bufs, batch_dims,
                                                   lowering)
            else:
                bufs[ins.dst] = self._dispatch(ins, bufs, batch_dims,
                                               lowering)
            i += 1
        missing = [o for o in prog.outputs if o not in bufs]
        if missing:
            raise KeyError(f"program did not produce outputs: {missing}")
        return {o: bufs[o] for o in prog.outputs}, lowering, fusion

    def run_async(self, prog: TMProgram, buffers, *, runtime, deps=(),
                  batch_dims: int = 0, label: str = "tm-program"):
        """Submit ``prog`` onto ``runtime``'s TMU stream instead of running
        it on the calling thread.

        ``buffers`` is the input dict, or a zero-arg callable resolved on
        the stream thread (so inputs produced by the ``deps`` events bind
        after those events complete).  Returns the
        :class:`~repro.runtime.streams.StreamEvent`; its result is this
        executor's ``(outputs, lowering, fusion)`` triple once the work —
        not merely its dispatch — has finished."""
        def task():
            bufs = buffers() if callable(buffers) else buffers
            return self.run(prog, bufs, batch_dims=batch_dims)
        return runtime.submit("tmu", task, deps=deps, label=label)

    def _run_chain(self, chain: ForwardChain, prog: TMProgram, bufs: dict,
                   batch_dims: int, lowering: LoweringReport) -> None:
        """Execute one chain region, fusing the longest claimable runs.

        Greedy: at each position try the longest remaining sub-chain (>= 2
        links) against the registry, shrinking from the tail; a claimed run
        executes as ONE kernel (its streamed intermediates are passed as
        ``None`` source slots and never enter the buffer file — only the
        run's final destination binds, which is exactly the handoff point
        when a suffix follows), an unclaimable head instruction lowers
        per-instruction and the scan advances one."""
        idxs = chain.instrs
        sb = self.params.segment_bytes if self.params is not None else None
        pos, n = 0, len(idxs)
        while pos < n:
            claimed = None
            for end in range(n, pos + 1, -1):
                if end - pos < 2:
                    break
                instrs = [prog.instrs[k] for k in idxs[pos:end]]
                streamed = set(chain.buffers[pos:end - 1])
                srcs = [[None if s in streamed else bufs[s]
                         for s in ins.srcs] for ins in instrs]
                lowered = lower_chain(instrs, srcs, batch_dims,
                                      segment_bytes=sb,
                                      quarantine=self.quarantine,
                                      declines=lowering.declines)
                if lowered is not None:
                    claimed = (end, lowered)
                    break
            if claimed is None:
                ins = prog.instrs[idxs[pos]]
                bufs[ins.dst] = self._dispatch(ins, bufs, batch_dims,
                                               lowering)
                pos += 1
                continue
            end, (val, rec) = claimed
            lowering.records.append(rec)
            bufs[prog.instrs[idxs[end - 1]].dst] = val
            pos = end

    def _dispatch(self, ins: TMInstr, bufs: dict, batch_dims: int,
                  lowering: LoweringReport) -> jnp.ndarray:
        # compiled programs pin per-instruction batch dims (the RME
        # legalization pass); an executor-level batch lift composes on top
        # (the caller's leading axes come before the instruction's own)
        if ins.meta and "batch_dims" in ins.meta and ins.opcode in (
                TMOpcode.FINE_ASSEMBLE, TMOpcode.FINE_EVALUATE):
            batch_dims = batch_dims + ins.meta["batch_dims"]
        if self.backend == "pallas":
            srcs = [bufs[s] for s in ins.srcs]  # Tensor Load
            sb = self.params.segment_bytes if self.params is not None else None
            faults: list | None = [] if self.quarantine is not None else None
            declines: list = []
            lowered = lower_instr(ins, srcs, batch_dims, segment_bytes=sb,
                                  quarantine=self.quarantine, faults=faults,
                                  declines=declines)
            if lowered is not None:
                val, rec = lowered
                lowering.records.append(rec)
                return val
            # the registry cannot tell us *why* every rule declined; report
            # the one observable condition without guessing at causes
            if faults:
                reason = ("degraded to engine fallback: "
                          + "; ".join(f"{name} {why}" for name, why in faults))
            elif declines:
                reason = "declined: " + "; ".join(
                    f"{name}: {why}" for name, why in declines)
            else:
                reason = (f"no matching kernel rule (batch_dims={batch_dims})"
                          if batch_dims else "no matching kernel rule")
            val = self._exec(ins, bufs, batch_dims)
            lowering.records.append(Lowering(
                dst=ins.dst, opcode=ins.opcode.value,
                path=f"reference.{ins.opcode.value}", reason=reason,
                degraded=bool(faults)))
            return val
        val = self._exec(ins, bufs, batch_dims)
        lowering.records.append(Lowering(
            dst=ins.dst, opcode=ins.opcode.value,
            path=f"reference.{ins.opcode.value}"))
        return val

    # one instruction = Decode + Load + (fine|ew|coarse) + Store
    def _exec(self, ins: TMInstr, bufs: dict, batch_dims: int) -> jnp.ndarray:
        srcs = [bufs[s] for s in ins.srcs]  # Tensor Load
        if ins.opcode == TMOpcode.COPY:
            return srcs[0]
        if ins.opcode == TMOpcode.ELEMENTWISE:
            return _EW[ins.ew](srcs[0], srcs[1])
        if ins.opcode == TMOpcode.COARSE:
            if ins.maps is not None:  # Route: band loop (Branch stage)
                overlay = bool(ins.meta and ins.meta.get("overlay"))
                out = route_gather(ins.maps, srcs, batch_dims=batch_dims,
                                   overlay=overlay)
                if ins.ew is not None and len(srcs) > len(ins.maps):
                    out = _EW[ins.ew](out, srcs[-1])
                return out
            out = apply_map(ins.map_, srcs[0], batch_dims=batch_dims)
            if ins.ew is not None:  # fused elementwise epilogue
                out = _EW[ins.ew](out, srcs[1])
            return out
        if ins.opcode == TMOpcode.RESIZE:
            from repro.core.tm_ops import resize_bilinear
            return resize_bilinear(srcs[0], ins.meta["out_h"], ins.meta["out_w"])
        if ins.opcode == TMOpcode.FINE_ASSEMBLE:
            cfg = ins.rme
            if cfg.lane_mask is not None:
                return rme.assemble_static(srcs[0], jnp.asarray(cfg.lane_mask, bool))
            fn = lambda x, m: rme.assemble(x, m.astype(bool), cfg.capacity)[0]
            return _vmap_leading(fn, batch_dims)(srcs[0], srcs[1])
        if ins.opcode == TMOpcode.FINE_EVALUATE:
            cfg = ins.rme
            if cfg.top_k is not None:
                fn = lambda x: rme.evaluate_topk(x, cfg.top_k, cfg.capacity,
                                                 cfg.score_index)[0]
            else:
                fn = lambda x: rme.evaluate(x, cfg.threshold, cfg.capacity,
                                            cmp=cfg.cmp,
                                            score_index=cfg.score_index)[0]
            return _vmap_leading(fn, batch_dims)(srcs[0])
        raise ValueError(f"unknown opcode {ins.opcode}")


def _vmap_leading(fn: Callable, batch_dims: int) -> Callable:
    """vmap ``fn`` over ``batch_dims`` leading axes of every argument — the
    reference engine's batch lift for the fine-grained (RME) stage."""
    for _ in range(batch_dims):
        fn = jax.vmap(fn)
    return fn
