"""``tm_compile`` — trace a JAX function into an optimized, scheduled program.

    compiled = tm_compile(fn, *example_args)
    y = compiled(*args)                      # bit-exact vs fn(*args)
    y = compiled(*args, backend="pallas")    # TM phases on the Pallas kernels
    print(compiled.report())                 # trace/pass/partition/scratch

The compiled object executes the partitioned phase DAG.  Opaque TPU phases
are each jitted as **one XLA computation** (dead intermediates donated, so
XLA reuses their buffers); TMU phases run through the
:class:`~repro.core.executor.TMExecutor` on any of the three backends — so
one compilation is differential-testable across reference / fused / pallas
exactly like a hand-written :class:`~repro.core.instr.TMProgram`.

Two execution modes share the same phase DAG:

* **blocking** (``run(*args)``) — walk the phases in program order on the
  calling thread; the honest single-engine baseline;
* **stream-ordered** (``run(*args, runtime=...)`` or
  :meth:`CompiledTMProgram.run_async`) — submit every phase onto its
  engine's stream (:mod:`repro.runtime.streams`) with its DAG in-edges as
  event dependencies.  Independent phases overlap across the TMU/TPU
  engines; the host synchronizes only at sinks.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.core.executor import TMExecutor
from repro.core.dispatch import Lowering, LoweringReport, lower_xengine
from repro.core.instr import TMProgram
from repro.core.schedule import CycleParams
from repro.core.tm_primitive import tag_tm_ops
from repro.obs.tracer import NULL_TRACER
from repro.compiler.allocate import ScratchPlan, allocate
from repro.compiler.ir import TMGraph, eval_tpu_node, eval_tpu_node_exact
from repro.compiler.partition import (
    _KIND_CHARS, PartitionReport, Phase, partition)
from repro.compiler.passes import PassReport, run_pipeline
from repro.compiler.trace import graph_from_jaxpr


# sentinel stored on Phase.jit_fn once jit staging failed for the phase —
# later executions go straight to the eager per-eqn fallback
_JIT_DECLINED = object()

# repro.ft.FaultInjector.install() points this at its fire() method; None in
# production — run_phase pays one attribute load per phase
fault_hook = None


@dataclasses.dataclass
class TPUPhaseReport:
    """Launch accounting for one opaque TPU phase execution.

    ``xla_computations`` is 1 when the phase ran through its jitted callable
    — the whole equation run is a single XLA computation per call (the
    compile-mode contract); the eager fallback binds each equation
    separately."""

    phase_index: int
    n_eqns: int
    jitted: bool
    xla_computations: int
    donated: tuple[str, ...] = ()


@dataclasses.dataclass
class CompiledTMProgram:
    """A traced, optimized, partitioned and scheduled program.

    ``params`` pins the cycle params the program was scheduled with; the TM
    phases execute with the same params, so a custom segment budget
    reconfigures the launched Pallas grids exactly as the model predicted
    (the serving runtime's per-entry config selection pins the winner here).
    """

    graph: TMGraph
    pass_report: PassReport
    partition_report: PartitionReport
    scratch_plan: ScratchPlan
    in_tree: Any
    out_tree: Any
    params: CycleParams | None = None
    last_lowering: list[LoweringReport] = dataclasses.field(
        default_factory=list)

    # --- introspection ----------------------------------------------------
    @property
    def tm_programs(self) -> list[TMProgram]:
        return [p.program for p in self.partition_report.tmu_phases]

    @property
    def matched_prims(self) -> set[str]:
        return set(self.graph.matched_prims)

    def report(self) -> str:
        return "\n".join([
            self.graph.summary(),
            self.pass_report.summary(),
            self.partition_report.summary(),
            self.scratch_plan.summary(),
        ])

    # --- TPU phases: one jitted XLA computation each ----------------------
    def _donatable(self, phase: Phase) -> tuple[int, ...]:
        """Argument positions of ``phase.reads`` safe to donate: buffers
        this phase is the SOLE consumer of (and that are not graph
        inputs/consts/outputs).  Sole-consumer is the schedule-independent
        condition — under stream dispatch a sibling phase that also reads
        the buffer may run concurrently, so "no later reader in program
        order" is not enough.  XLA may then write outputs into the donated
        buffers."""
        pinned = (set(self.graph.inputs) | set(self.graph.consts)
                  | set(self.graph.outputs))
        other_reads = {name for ph in self.partition_report.phases
                       if ph.index != phase.index for name in ph.reads}
        return tuple(i for i, name in enumerate(phase.reads)
                     if name not in pinned and name not in other_reads)

    def _tpu_phase_fn(self, phase: Phase):
        """The phase's jitted callable (built once, cached on the phase —
        repeat executions and warm serving entries reuse the executable).
        The donated-name tuple is cached alongside it."""
        if phase.jit_fn is None:
            nodes = [self.graph.nodes[i] for i in phase.node_indices]
            reads, writes = phase.reads, phase.writes

            def phase_fn(*vals):
                env = dict(zip(reads, vals))
                for node in nodes:
                    eval_tpu_node(node, env)
                return tuple(env[n] for n in writes)

            # buffer donation only exists on accelerator backends; on CPU
            # XLA refuses the aliasing and jax warns per compile — so only
            # donate where the donation is real
            donate = (self._donatable(phase)
                      if jax.default_backend() in ("tpu", "gpu") else ())
            phase.donated = tuple(phase.reads[i] for i in donate)
            phase.jit_fn = jax.jit(phase_fn, donate_argnums=donate)
        return phase.jit_fn

    # --- execution --------------------------------------------------------
    # Split into bind_inputs / run_phase / outputs_from so the serving
    # pipeline can dispatch one program's phases through the engine streams.

    def bind_inputs(self, *args) -> dict[str, Any]:
        """Validate ``args`` against the compiled signature; return the
        initial buffer environment (consts + bound inputs)."""
        flat, tree = jax.tree_util.tree_flatten(args)
        if tree != self.in_tree:
            raise TypeError(f"argument structure {tree} does not match the "
                            f"compiled structure {self.in_tree}")
        if len(flat) != len(self.graph.inputs):
            raise TypeError(f"expected {len(self.graph.inputs)} input "
                            f"array(s), got {len(flat)}")
        env: dict[str, Any] = dict(self.graph.consts)
        for name, val in zip(self.graph.inputs, flat):
            val = jax.numpy.asarray(val)
            want = self.graph.buffers[name]
            if tuple(val.shape) != want.shape or val.dtype != want.dtype:
                raise TypeError(
                    f"input {name!r}: {val.dtype}{tuple(val.shape)} does "
                    f"not match compiled {want.dtype}{want.shape}; "
                    f"recompile with tm_compile for new shapes/dtypes")
            env[name] = val
        return env

    def _phase_hbm_bytes(self, phase: Phase) -> int:
        """Data-movement estimate of one phase execution: every external
        read plus every downstream-visible write through HBM once.
        Memoized per phase."""
        cache = self.__dict__.setdefault("_hbm_bytes_cache", {})
        total = cache.get(phase.index)
        if total is None:
            import numpy as np
            total = 0
            for name in tuple(phase.reads) + tuple(phase.writes):
                buf = self.graph.buffers[name]
                n = int(np.dtype(buf.dtype).itemsize)
                for d in buf.shape:
                    n *= int(d)
                total += n
            cache[phase.index] = total
        return total

    def run_phase(self, phase: Phase, env: dict[str, Any], *,
                  backend: str = "fused",
                  fuse_chains: bool = False,
                  exact: bool = False,
                  tracer=None,
                  quarantine: set | None = None,
                  ) -> LoweringReport | TPUPhaseReport:
        """Execute one partition phase against ``env`` (mutated in place).

        A TPU phase runs its jitted callable — ONE XLA computation per call,
        dead intermediates donated — and returns a :class:`TPUPhaseReport`;
        a TMU phase runs through the executor and returns its
        :class:`~repro.core.dispatch.LoweringReport`.  ``fuse_chains``
        (pallas backend) executes each forwarding chain of the phase as ONE
        segment-streaming kernel — the streamed buffers of the scratch plan
        never materialize.

        ``exact`` trades the one-computation-per-phase contract for bit-exact
        parity with the eager program: each TPU eqn runs as its own XLA
        computation with its literals baked
        (:func:`~repro.compiler.ir.eval_tpu_node_exact`), matching eager
        dispatch granularity so XLA's cross-op algebraic rewrites (the
        ``rsqrt(x/c + e)`` class) cannot perturb the rounding.  TM phases are
        data movement and are bit-exact in every mode.

        ``tracer`` (a :class:`repro.obs.Tracer`) wraps the execution in a
        ``phase/{index}/{kind}`` span; at ``Tracer(detail="instr")`` the
        span also carries the phase's launch/segment accounting and the
        ``tmu/launches``, ``tmu/segments`` and ``tpu/xla_computations``
        counters accumulate (evaluating that payload per phase is NOT free,
        which is why the default "phase" detail records the bare interval);
        the default no-op tracer costs one attribute check.

        ``quarantine`` (the owning cache entry's mutable set) arms the
        kernel degradation ladder on the pallas backend — see
        :func:`repro.core.dispatch.lower_instr`."""
        hook = fault_hook
        if hook is not None:
            hook("phase", f"phase/{phase.index}/{phase.kind}")
        tracer = NULL_TRACER if tracer is None else tracer
        if not tracer.enabled:
            return self._exec_phase(phase, env, backend=backend,
                                    fuse_chains=fuse_chains, exact=exact,
                                    quarantine=quarantine)
        with tracer.span(f"phase/{phase.index}/{phase.kind}",
                         backend=backend) as sp:
            rep = self._exec_phase(phase, env, backend=backend,
                                   fuse_chains=fuse_chains, exact=exact,
                                   tracer=tracer, quarantine=quarantine)
            if tracer.detail == "instr":
                if isinstance(rep, TPUPhaseReport):
                    sp.set(n_eqns=rep.n_eqns, jitted=rep.jitted,
                           xla_computations=rep.xla_computations)
                    tracer.count("tpu/xla_computations",
                                 rep.xla_computations)
                else:
                    launches = rep.launch_count()
                    segments = sum(r.segments or 0 for r in rep.records)
                    sp.set(instrs=rep.instr_count(), launches=launches,
                           segments=segments, chains=rep.chain_count())
                    tracer.count("tmu/launches", launches)
                    tracer.count("tmu/segments", segments)
        return rep

    def _exec_phase(self, phase: Phase, env: dict[str, Any], *,
                    backend: str, fuse_chains: bool,
                    exact: bool, tracer=NULL_TRACER,
                    quarantine: set | None = None,
                    ) -> LoweringReport | TPUPhaseReport:
        if phase.kind == "fused":
            return self._exec_fused(phase, env, backend=backend,
                                    fuse_chains=fuse_chains, exact=exact,
                                    tracer=tracer, quarantine=quarantine)
        if phase.kind == "tpu":
            if exact:
                for i in phase.node_indices:
                    eval_tpu_node_exact(self.graph.nodes[i], env)
                return TPUPhaseReport(
                    phase_index=phase.index,
                    n_eqns=len(phase.node_indices),
                    jitted=False,
                    xla_computations=len(phase.node_indices))
            if phase.jit_fn is not _JIT_DECLINED:
                try:
                    outs = self._tpu_phase_fn(phase)(
                        *[env[n] for n in phase.reads])
                except Exception:
                    if phase.jit_ok:
                        # the executable has worked before: this is a
                        # genuine runtime/data error, not a staging refusal
                        # — propagate it instead of silently degrading the
                        # warm entry to per-eqn execution forever
                        raise
                    # never staged successfully (host callbacks, impure
                    # prims): remember the decline so warm calls skip
                    # straight to eager instead of re-paying a failing
                    # trace; a genuine data error re-raises from eager
                    phase.jit_fn = _JIT_DECLINED
                else:
                    phase.jit_ok = True
                    env.update(zip(phase.writes, outs))
                    return TPUPhaseReport(
                        phase_index=phase.index,
                        n_eqns=len(phase.node_indices),
                        jitted=True, xla_computations=1,
                        donated=phase.donated or ())
            for i in phase.node_indices:   # eager per-eqn binding, bit-exact
                eval_tpu_node(self.graph.nodes[i], env)
            return TPUPhaseReport(
                phase_index=phase.index, n_eqns=len(phase.node_indices),
                jitted=False, xla_computations=len(phase.node_indices))
        ex = TMExecutor(backend=backend, params=self.params,
                        fuse_chains=fuse_chains, tracer=tracer,
                        quarantine=quarantine)
        bufs = {n: env[n] for n in phase.program.inputs}
        out, lowering, _ = ex.run(phase.program, bufs)
        env.update(out)
        return lowering

    def _exec_fused(self, phase: Phase, env: dict[str, Any], *,
                    backend: str, fuse_chains: bool,
                    exact: bool, tracer=NULL_TRACER,
                    quarantine: set | None = None) -> LoweringReport:
        """Execute a cross-engine fused phase: the compute eqn + its TM run
        as ONE Pallas launch (pallas backend), with the crossing buffer
        streamed through VMEM; any decline — unsupported geometry, VMEM
        budget, a quarantined kernel, the reference/fused backends, exact
        mode — takes the split path (eqn and TM run separately), bit-exact.
        The partition only emits fused phases under ``cross_engine=True``,
        which is itself an opt-in (the serving sweep pins it only after a
        realized probe), so the pallas path needs no further gating."""
        xe = phase.xengine
        node = self.graph.nodes[xe.eqn_index]
        instrs = [self.graph.nodes[i].instr for i in xe.tm_indices]
        direction = xe.direction
        report = LoweringReport(backend=backend)
        if backend == "pallas" and not exact:
            streamed = set(xe.chain.buffers) | {xe.buffer}
            tm_srcs = [[None if s in streamed else env[s] for s in ins.srcs]
                       for ins in instrs]
            eqn_srcs = [lit if s is None
                        else (None if s == xe.buffer else env[s])
                        for s, lit in zip(node.src_names, node.literals)]
            sb = self.params.segment_bytes if self.params is not None \
                else None
            lowered = lower_xengine(direction, node, eqn_srcs, instrs,
                                    tm_srcs, segment_bytes=sb,
                                    quarantine=quarantine,
                                    declines=report.declines)
            if lowered is not None:
                val, rec = lowered
                env[rec.dst] = val
                report.records.append(rec)
                return report
        # split path: evaluate the eqn and the TM run in dataflow order —
        # exactly what the non-crossing partition executes
        def run_eqn():
            if exact:
                eval_tpu_node_exact(node, env)
            else:
                eval_tpu_node(node, env)
            report.records.append(Lowering(
                dst=node.dst_names[0], opcode="tpu",
                path=f"xla.{node.primitive_name}",
                reason="; ".join(["cross-engine lowering declined: split "
                                  "path"] + report.declines)))

        def run_tm():
            ex = TMExecutor(backend=backend, params=self.params,
                            fuse_chains=fuse_chains, tracer=tracer,
                            quarantine=quarantine)
            bufs = {n: env[n] for n in phase.program.inputs}
            out, lowering, _ = ex.run(phase.program, bufs)
            env.update(out)
            report.records.extend(lowering.records)
            report.declines.extend(lowering.declines)

        if direction == "compute_to_tm":
            run_eqn()
            run_tm()
        else:
            run_tm()
            run_eqn()
        return report

    def outputs_from(self, env: dict[str, Any]):
        outs = [env[o] for o in self.graph.outputs]
        return jax.tree_util.tree_unflatten(self.out_tree, outs)

    def run_async(self, env: dict[str, Any], *, runtime,
                  backend: str = "fused",
                  fuse_chains: bool = False, exact: bool = False,
                  label: str = "", tracer=None,
                  quarantine: set | None = None):
        """Submit every phase of the DAG onto ``runtime``'s engine streams.

        Each phase becomes one stream task whose event dependencies are its
        DAG in-edges (``phase.deps``) — independent phases overlap across
        the TMU/TPU streams, and nothing blocks the calling thread.  Tasks
        communicate through the shared ``env``: a producer binds its writes
        before its event completes, so a consumer's reads are
        happens-before-ordered by the event wait (buffer names are SSA —
        no two phases write the same key).

        Returns the phase events in phase order; each completed event's
        ``result`` is ``(written arrays, LoweringReport | TPUPhaseReport)``.
        Wait the sink events (or all of them) to synchronize."""
        events = []
        for phase in self.partition_report.phases:
            def task(ph=phase):
                rep = self.run_phase(ph, env, backend=backend,
                                     fuse_chains=fuse_chains, exact=exact,
                                     tracer=tracer, quarantine=quarantine)
                return [env[n] for n in ph.writes], rep
            events.append(runtime.submit(
                phase.engine, task, deps=[events[d] for d in phase.deps],
                label=f"{label}phase{phase.index}:{phase.kind}"))
        return events

    def run(self, *args, backend: str = "fused",
            fuse_chains: bool = False, exact: bool = False, runtime=None,
            tracer=None, quarantine: set | None = None,
            ) -> tuple[Any, list[LoweringReport]]:
        """Execute and return ``(outputs, per-TM-phase lowering reports)``.

        With ``runtime`` (a :class:`~repro.runtime.streams.StreamRuntime`)
        the phase DAG dispatches stream-ordered and this call synchronizes
        only at the sinks; without it the phases run blocking, in program
        order, on this thread.  Mutates no state on ``self`` — safe under
        concurrent callers (the serving runtime's worker threads);
        :meth:`__call__` wraps this and keeps ``last_lowering`` as an alias
        for the last call."""
        env = self.bind_inputs(*args)
        reports: list[LoweringReport | TPUPhaseReport] = []
        if runtime is not None:
            events = self.run_async(env, runtime=runtime, backend=backend,
                                    fuse_chains=fuse_chains, exact=exact,
                                    tracer=tracer, quarantine=quarantine)
            for ev in events:   # sink sync: deps complete transitively
                reports.append(ev.wait()[1])
        else:
            for phase in self.partition_report.phases:
                reports.append(self.run_phase(phase, env, backend=backend,
                                              fuse_chains=fuse_chains,
                                              exact=exact, tracer=tracer,
                                              quarantine=quarantine))
        lowerings = [r for r in reports if isinstance(r, LoweringReport)]
        return self.outputs_from(env), lowerings

    def __call__(self, *args, backend: str = "fused",
                 fuse_chains: bool = False,
                 exact: bool = False, runtime=None, tracer=None):
        out, lowerings = self.run(*args, backend=backend,
                                  fuse_chains=fuse_chains, exact=exact,
                                  runtime=runtime, tracer=tracer)
        self.last_lowering = lowerings
        return out


def tm_compile(fn, *example_args, params: CycleParams | None = None,
               cross_engine: bool = False, tracer=None) -> CompiledTMProgram:
    """Trace ``fn`` at ``example_args`` and lower it through the pipeline:

    jaxpr -> TM IR (trace) -> passes (map composition, copy elim, epilogue
    sink, RME legalization) -> TPU/TMU phase DAG + pipeline schedule ->
    scratch allocation.

    ``cross_engine`` lets the partition merge legal engine-boundary
    crossings (a supported compute eqn forwarding into — or fed by — an
    adjacent COARSE TM run) into single ``fused`` phases that lower as ONE
    Pallas launch; off by default so the phase DAG of non-crossing programs
    is byte-identical with the flag in either state.

    ``tracer`` (a :class:`repro.obs.Tracer`) records each stage as a nested
    span under ``compile`` with the stage's report summary attached, and
    counts weight-only TM work hoisted out of the program at trace time
    (``compile/const_hoist``; ``compile/const_hoist_shared`` for folds
    another program over the same weights made first).
    """
    tracer = NULL_TRACER if tracer is None else tracer
    flat_in, in_tree = jax.tree_util.tree_flatten(example_args)
    with tracer.span("compile") as root:
        with tracer.span("compile/trace") as sp:
            with tag_tm_ops():
                closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
                    *example_args)
            out_tree = jax.tree_util.tree_structure(out_shape)
            graph = graph_from_jaxpr(closed)
            sp.set(summary=graph.summary(), const_hoist_bytes=sum(
                h.nbytes for h in graph.hoisted))
        if graph.hoisted:
            shared = sum(h.shared for h in graph.hoisted)
            tracer.count("compile/const_hoist", len(graph.hoisted) - shared)
            tracer.count("compile/const_hoist_shared", shared)
        with tracer.span("compile/passes") as sp:
            pass_report = run_pipeline(graph)
            sp.set(summary=pass_report.summary())
        with tracer.span("compile/partition") as sp:
            part = partition(graph, params, cross_engine=cross_engine)
            sp.set(summary=part.summary(), phases=len(part.phases),
                   dag_edges=part.dag_edges)
        with tracer.span("compile/allocate") as sp:
            scratch = allocate(graph, part, params)
            sp.set(summary=scratch.summary())
        root.set(phases="".join(_KIND_CHARS.get(p.kind, "?")
                                for p in part.phases))
    return CompiledTMProgram(graph=graph, pass_report=pass_report,
                             partition_report=part, scratch_plan=scratch,
                             in_tree=in_tree, out_tree=out_tree,
                             params=params)
