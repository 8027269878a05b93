"""TM IR — the compiler's program graph.

A :class:`TMGraph` is an ordered list of nodes over a buffer file:

* :class:`TMNode` — one TM instruction (:class:`~repro.core.instr.TMInstr`),
  destined for the TMU datapath (executed by the
  :class:`~repro.core.executor.TMExecutor` backends);
* :class:`TPUNode` — one opaque jaxpr equation (dot_general, conv, tanh, …),
  destined for the TPU; the compiler never looks inside, it only tracks the
  def/use edges.

Buffers are named SSA values with shape/dtype (from the trace's avals).
Node order is the original program order — passes rewrite nodes in place and
the partitioner groups maximal same-kind runs into phases.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax

from repro.core.instr import TMInstr


@dataclasses.dataclass(frozen=True)
class Buffer:
    name: str
    shape: tuple[int, ...]
    dtype: Any  # numpy dtype (from the aval)


@dataclasses.dataclass
class TMNode:
    """One TM instruction; ``instr.srcs``/``instr.dst`` name graph buffers."""

    instr: TMInstr
    matched: str = ""  # the jaxpr primitive this node was matched from

    @property
    def srcs(self) -> tuple[str, ...]:
        return self.instr.srcs

    @property
    def dsts(self) -> tuple[str, ...]:
        return (self.instr.dst,)

    @property
    def kind(self) -> str:
        return "tmu"


@dataclasses.dataclass
class TPUNode:
    """One opaque jaxpr eqn, evaluated by re-binding the primitive.

    ``src_names[i]`` is None where ``literals[i]`` holds an inline literal
    operand instead of a buffer read.
    """

    eqn: Any  # jax JaxprEqn
    src_names: tuple[str | None, ...]
    literals: tuple[Any, ...]
    dst_names: tuple[str, ...]
    # per-eqn jitted evaluator with literals baked (exact mode); built lazily
    exact_fn: Any = dataclasses.field(default=None, repr=False, compare=False)

    @property
    def srcs(self) -> tuple[str, ...]:
        return tuple(s for s in self.src_names if s is not None)

    @property
    def dsts(self) -> tuple[str, ...]:
        return self.dst_names

    @property
    def kind(self) -> str:
        return "tpu"

    @property
    def primitive_name(self) -> str:
        return self.eqn.primitive.name


def eval_tpu_node(node: TPUNode, env: dict) -> None:
    """Execute one opaque eqn by re-binding its primitive; results land in
    ``env`` under the node's dst names."""
    invals = [env[s] if s is not None else lit
              for s, lit in zip(node.src_names, node.literals)]
    eqn = node.eqn
    subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
    out = eqn.primitive.bind(*subfuns, *invals, **bind_params)
    outs = out if eqn.primitive.multiple_results else [out]
    for name, val in zip(node.dst_names, outs):
        env[name] = val


def eval_tpu_node_exact(node: TPUNode, env: dict) -> None:
    """Execute one opaque eqn bit-exactly vs the eager program.

    Two things separate this from :func:`eval_tpu_node` under a whole-phase
    jit, and both change float rounding:

    * **literals are baked**, not passed as runtime scalars.  Eager jnp code
      bakes its constants into each dispatched XLA computation, where the
      algebraic simplifier applies constant rewrites (``x / 48`` becomes
      ``x * (1/48)``); a literal arriving as an argument stays a true
      division and rounds differently;
    * **one XLA computation per eqn**, matching eager's dispatch granularity.
      Fusing a phase like ``div → add → rsqrt`` into one computation lets the
      simplifier rewrite across the ops (observed: the fused ``rsqrt(x/c+e)``
      chain differs from the op-by-op result by 1 ulp), which is exactly the
      divergence a bit-exact decode gate cannot absorb.

    The per-eqn jitted evaluator is cached on the node, so warm serving
    entries pay the trace once per eqn."""
    if node.exact_fn is None:
        eqn = node.eqn
        src_names, literals = node.src_names, node.literals

        def eqn_fn(*vals):
            it = iter(vals)
            invals = [next(it) if s is not None else lit
                      for s, lit in zip(src_names, literals)]
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            return eqn.primitive.bind(*subfuns, *invals, **bind_params)

        node.exact_fn = jax.jit(eqn_fn)
    out = node.exact_fn(*[env[s] for s in node.src_names if s is not None])
    outs = out if node.eqn.primitive.multiple_results else [out]
    for name, val in zip(node.dst_names, outs):
        env[name] = val


@dataclasses.dataclass
class TMGraph:
    """The compiler's unit of work: ordered nodes + buffer declarations."""

    nodes: list  # list[TMNode | TPUNode]
    buffers: dict[str, Buffer]
    inputs: tuple[str, ...]
    outputs: tuple[str, ...]
    consts: dict[str, Any]  # const buffers -> concrete values
    matched_prims: set[str] = dataclasses.field(default_factory=set)
    # trace-time fallback notes: matchable-looking eqns the front end left
    # opaque (traced dynamic_slice starts, matcher errors, …) — surfaced by
    # the pass report so compilations explain their TPU residue
    notes: list = dataclasses.field(default_factory=list)
    # weight-only TM eqns the front end folded into consts above the fold
    # limit (``trace.Hoist`` records) — surfaced by the pass report
    hoisted: list = dataclasses.field(default_factory=list)

    # --- queries ----------------------------------------------------------
    def producer_index(self, name: str, before: int | None = None) -> int | None:
        """Index of the last node writing ``name`` before position ``before``."""
        hi = len(self.nodes) if before is None else before
        for i in range(hi - 1, -1, -1):
            if name in self.nodes[i].dsts:
                return i
        return None

    def consumer_indices(self, name: str, after: int = -1) -> list[int]:
        return [i for i, n in enumerate(self.nodes)
                if i > after and name in n.srcs]

    def shape(self, name: str) -> tuple[int, ...]:
        return self.buffers[name].shape

    def tm_nodes(self) -> list[TMNode]:
        return [n for n in self.nodes if n.kind == "tmu"]

    def tpu_nodes(self) -> list[TPUNode]:
        return [n for n in self.nodes if n.kind == "tpu"]

    def validate(self) -> None:
        """Every read is defined upstream (input/const or earlier dst)."""
        defined = set(self.inputs) | set(self.consts)
        for i, n in enumerate(self.nodes):
            for s in n.srcs:
                if s not in defined:
                    raise ValueError(
                        f"node {i} ({n.kind}) reads undefined buffer {s!r}")
            defined.update(n.dsts)
        for o in self.outputs:
            if o not in defined:
                raise ValueError(f"graph output {o!r} is never defined")

    def summary(self) -> str:
        tm = len(self.tm_nodes())
        tpu = len(self.tpu_nodes())
        base = (f"TMGraph: {tm} TM instr(s), {tpu} TPU node(s), "
                f"{len(self.buffers)} buffers, "
                f"matched prims: {sorted(self.matched_prims)}")
        if self.notes:
            base += f", {len(self.notes)} trace note(s)"
        return base
