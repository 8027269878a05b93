"""Kernel-dispatch registry — lowering TM instructions onto Pallas kernels.

The TMU decodes each instruction's register contents and drives one of its
datapaths; the TPU-native analogue is *lowering*: each :class:`TMInstr` is
matched against a registry of kernel rules (populated by the kernel packages
under :mod:`repro.kernels` at import time) and executed by the first rule
that claims it.  Instructions no rule claims fall back to the generic engine
(:func:`repro.core.engine.apply_map` et al.) — exactly like a TMU raising a
configuration it does not support to the host.

Every lowering decision is recorded as a :class:`Lowering` in a
:class:`LoweringReport`, so tests and benchmarks can assert *which* datapath
ran (block-mode DMA, gather kernel, RME compaction, …), not just that the
numbers agree.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core.instr import TMInstr
from repro.platform import pallas_interpret

# repro.ft.FaultInjector.install() points this at its fire() method; None in
# production.  It fires INSIDE the rule-execution try below, so an injected
# lowering fault exercises the quarantine/fallback ladder, not a crash.
fault_hook: Callable[[str, str], None] | None = None


class Decline(str):
    """What a rule returns instead of a path (``matches``) or a result
    (chain/cross-engine ``lower``) when it recognizes the work but will not
    launch it on this platform — the string says why, and the caller's
    fallback record carries it.  Deciding up front keeps a compiler refusal
    from ever reaching a launch."""


@dataclasses.dataclass(frozen=True)
class Lowering:
    """One lowering decision — an instruction, or a fused forwarding chain.

    ``launches`` makes kernel-launch accounting explicit (it used to be
    implicit: one per record): a block/gather kernel is one launch, a
    multi-band Route launches once per band, a reference fallback is one
    engine pass, and a fused chain is ONE launch covering ``instrs``
    instructions — the honest chained-vs-unchained comparison the
    forwarding benchmark gates on.
    """

    dst: str
    opcode: str
    path: str        # e.g. "pallas.block", "pallas.chain", "reference.coarse"
    kernel: str = ""  # registry rule that claimed the instruction ("" = fallback)
    reason: str = ""  # why the fallback was taken ("" when a kernel ran)
    segments: int | None = None  # kernel grid size (block iterations), when
    #                              the rule reports it — equals the cycle
    #                              model's count via schedule.map_segments /
    #                              instr_segments (pass batch_shape for
    #                              executor-level batch lifts)
    launches: int = 1  # kernel launches (engine passes for fallbacks)
    instrs: int = 1    # TM instructions this record covers (>1: fused chain)
    degraded: bool = False  # a preferred kernel failed/was quarantined and
    #                         this record is the surviving fallback path

    @property
    def is_pallas(self) -> bool:
        return self.path.startswith("pallas.")

    @property
    def is_chain(self) -> bool:
        return self.instrs > 1


@dataclasses.dataclass
class LoweringReport:
    """Per-instruction lowering decisions for one executor run."""

    backend: str
    records: list[Lowering] = dataclasses.field(default_factory=list)
    # "rule: why" for chain rules that declined up front (the chain's links
    # then lower one by one, each with its own record)
    declines: list[str] = dataclasses.field(default_factory=list)

    def paths(self) -> list[str]:
        return [r.path for r in self.records]

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for r in self.records:
            out[r.path] = out.get(r.path, 0) + 1
        return out

    def pallas_fraction(self) -> float:
        if not self.records:
            return 0.0
        return sum(r.is_pallas for r in self.records) / len(self.records)

    def launch_count(self) -> int:
        """Total kernel launches (engine passes for fallbacks) this run."""
        return sum(r.launches for r in self.records)

    def instr_count(self) -> int:
        """TM instructions executed (chain records cover several)."""
        return sum(r.instrs for r in self.records)

    def chain_count(self) -> int:
        """Fused forwarding chains executed as single kernels."""
        return sum(1 for r in self.records if r.is_chain)

    def degraded_count(self) -> int:
        """Records that took a fallback because a kernel failed or was
        quarantined (the degradation ladder's per-run footprint)."""
        return sum(1 for r in self.records if r.degraded)


@dataclasses.dataclass(frozen=True)
class KernelRule:
    """One registry entry.

    ``matches(ins, srcs, batch_dims, segment_bytes=None)`` returns the
    lowering path string when the rule can execute the instruction (None
    otherwise); ``run(ins, srcs, batch_dims, interpret, segment_bytes=None)``
    executes it, with ``interpret`` decided by
    :func:`repro.platform.pallas_interpret` from the operands.
    ``segment_bytes`` is the ping-pong buffer budget
    (:class:`~repro.core.schedule.CycleParams.segment_bytes`); None means the
    default — rules whose grids honour the budget re-segment from it, the
    rest accept and ignore it.  ``priority`` orders rules (higher first) so
    specialised kernels (img2col, resize) outrank the generic tm_affine
    gather.
    """

    name: str
    matches: Callable[..., str | None]
    run: Callable[..., jnp.ndarray]
    priority: int = 0
    # optional: report the grid size (block iterations) the kernel will run,
    # so the lowering report can be checked against the schedule's cycle model
    segments: Callable[..., int] | None = None
    # optional: kernel launches this rule issues (default 1; Route launches
    # one kernel per band)
    launches: Callable[..., int] | None = None


@dataclasses.dataclass(frozen=True)
class ChainRule:
    """One chain-registry entry — lowers a whole forwarding chain.

    ``lower(instrs, srcs, batch_dims, interpret, segment_bytes=None)``
    receives the chain's instruction run and each instruction's resolved
    sources (``None`` in the slot of a chain-internal intermediate — it
    never materializes).  It returns ``(value, path, segments)`` when the
    rule can execute the chain as ONE kernel, None otherwise — a single
    entry point so legality analysis runs once per call, not once per
    matches/run/segments hook.
    """

    name: str
    lower: Callable[..., tuple[jnp.ndarray, str, int | None] | None]
    priority: int = 0


@dataclasses.dataclass(frozen=True)
class XEngineRule:
    """One cross-engine registry entry — lowers a compute eqn plus its
    adjacent TM chain as ONE Pallas launch.

    ``lower(direction, eqn_node, eqn_srcs, instrs, tm_srcs, interpret,
    segment_bytes=None)`` receives the crossing direction
    (``"compute_to_tm"`` | ``"tm_to_compute"``), the TPU node
    (:class:`repro.compiler.ir.TPUNode`), the eqn's resolved operands
    (``None`` in the crossing slot for TM→compute; literal slots carry the
    literal value), the TM instruction run, and each TM instruction's
    resolved sources (``None`` for chain-internal intermediates AND for the
    crossing buffer — neither materializes).  Returns ``(value, path,
    segments)`` when the rule claims the crossing, None to decline (the
    caller splits, bit-exact)."""

    name: str
    lower: Callable[..., tuple[jnp.ndarray, str, int | None] | None]
    priority: int = 0


_RULES: list[KernelRule] = []
_CHAIN_RULES: list[ChainRule] = []
_XENGINE_RULES: list[XEngineRule] = []
_REGISTERED = False


def register_rule(name: str, matches, run, priority: int = 0,
                  segments=None, launches=None) -> None:
    """Register a kernel rule (called by kernel packages at import time)."""
    global _RULES
    _RULES = [r for r in _RULES if r.name != name]  # idempotent re-import
    _RULES.append(KernelRule(name, matches, run, priority, segments, launches))
    _RULES.sort(key=lambda r: -r.priority)


def register_chain_rule(name: str, lower, priority: int = 0) -> None:
    """Register a chain rule (called by kernel packages at import time)."""
    global _CHAIN_RULES
    _CHAIN_RULES = [r for r in _CHAIN_RULES if r.name != name]
    _CHAIN_RULES.append(ChainRule(name, lower, priority))
    _CHAIN_RULES.sort(key=lambda r: -r.priority)


def register_xengine_rule(name: str, lower, priority: int = 0) -> None:
    """Register a cross-engine rule (called by kernel packages at import)."""
    global _XENGINE_RULES
    _XENGINE_RULES = [r for r in _XENGINE_RULES if r.name != name]
    _XENGINE_RULES.append(XEngineRule(name, lower, priority))
    _XENGINE_RULES.sort(key=lambda r: -r.priority)


def _ensure_registered() -> None:
    """Import the kernel packages so their ops modules self-register."""
    global _REGISTERED
    if _REGISTERED:
        return
    import repro.kernels.img2col.ops    # noqa: F401
    import repro.kernels.resize.ops     # noqa: F401
    import repro.kernels.rme_gather.ops  # noqa: F401
    import repro.kernels.tm_affine.ops  # noqa: F401
    import repro.kernels.matmul_tm.chain  # noqa: F401
    _REGISTERED = True


def rules() -> list[KernelRule]:
    _ensure_registered()
    return list(_RULES)


def quarantine_key(rule_name: str, opcode: str,
                   srcs: Sequence[jnp.ndarray | None]) -> tuple:
    """The (rule, shape-class) identity a failing kernel is quarantined
    under: same rule + same opcode + same source shapes means the same
    lowering and is skipped without re-failing."""
    shapes = tuple(tuple(int(d) for d in getattr(s, "shape", ()))
                   for s in srcs if s is not None)
    return (rule_name, opcode, shapes)


def _arrays(*groups):
    """The array operands among nested source lists (None slots, literals
    and scalars dropped) — what the interpret decision looks at."""
    for g in groups:
        for s in g:
            if isinstance(s, (list, tuple)):
                yield from _arrays(s)
            elif isinstance(s, jax.Array):
                yield s


def lower_instr(ins: TMInstr, srcs: Sequence[jnp.ndarray], batch_dims: int,
                segment_bytes: int | None = None,
                quarantine: set | None = None,
                faults: list | None = None,
                declines: list | None = None,
                ) -> tuple[jnp.ndarray, Lowering] | None:
    """Lower one instruction through the registry.

    Returns ``(value, lowering)`` from the first matching rule, or None when
    no rule claims the instruction (caller falls back to the engine).
    ``segment_bytes`` propagates a custom ping-pong budget into the kernels
    (None = the :class:`~repro.core.schedule.CycleParams` default), so a
    non-default budget reconfigures the launched grids, not just the model.

    ``quarantine`` (a mutable set owned by the caller, usually the compile
    cache entry) arms the degradation ladder: a rule whose
    :func:`quarantine_key` is in the set is skipped outright, and a rule
    that *raises* is added to the set and skipped — lowering falls through
    to the next rule, or to the caller's engine fallback, and the surviving
    record is marked ``degraded``.  Without a quarantine set (the default)
    a raising rule propagates, preserving fail-fast semantics for direct
    executor use.  ``faults`` (optional caller-owned list) collects one
    ``(rule name, why)`` row per skipped rule, so a None return can still
    tell the caller its engine fallback is a degradation.  ``declines``
    (optional caller-owned list) collects ``(rule name, why)`` for rules
    that returned a :class:`Decline` — a platform limit known before any
    launch, not a fault.
    """
    _ensure_registered()
    interpret = pallas_interpret(*_arrays(srcs))
    degraded = False
    for rule in _RULES:
        path = rule.matches(ins, srcs, batch_dims, segment_bytes=segment_bytes)
        if path is None:
            continue
        if isinstance(path, Decline):
            if declines is not None:
                declines.append((rule.name, str(path)))
            continue
        if quarantine is not None:
            qkey = quarantine_key(rule.name, ins.opcode.value, srcs)
            if qkey in quarantine:
                degraded = True
                if faults is not None:
                    faults.append((rule.name, "quarantined"))
                continue
        try:
            hook = fault_hook
            if hook is not None:
                hook("lowering", f"{rule.name}:{ins.opcode.value}:{ins.dst}")
            val = rule.run(ins, srcs, batch_dims, interpret,
                           segment_bytes=segment_bytes)
        except Exception as e:
            if quarantine is None:
                raise
            quarantine.add(quarantine_key(rule.name, ins.opcode.value, srcs))
            degraded = True
            if faults is not None:
                faults.append((rule.name, f"failed: {e!r}"))
            continue
        seg = (rule.segments(ins, srcs, batch_dims,
                             segment_bytes=segment_bytes)
               if rule.segments is not None else None)
        n_launch = (rule.launches(ins, srcs, batch_dims)
                    if rule.launches is not None else 1)
        return val, Lowering(dst=ins.dst, opcode=ins.opcode.value,
                             path=path, kernel=rule.name, segments=seg,
                             launches=n_launch, degraded=degraded,
                             reason=("degraded: preferred kernel "
                                     "failed or quarantined"
                                     if degraded else ""))
    return None


def lower_chain(instrs: Sequence[TMInstr],
                srcs: Sequence[Sequence[jnp.ndarray | None]],
                batch_dims: int,
                segment_bytes: int | None = None,
                quarantine: set | None = None,
                declines: list | None = None,
                ) -> tuple[jnp.ndarray, Lowering] | None:
    """Lower a whole forwarding chain through the chain registry.

    ``instrs`` is the chain's consecutive instruction run
    (:func:`repro.core.fusion.forwarding_chains`); ``srcs[k]`` resolves
    instruction k's sources, with ``None`` in the position of the streamed
    intermediate (it has no buffer — that is the point).  Returns
    ``(final value, lowering)`` from the first rule that claims the chain —
    one record, ``launches=1``, covering ``len(instrs)`` instructions — or
    None when no rule does (caller executes the links one by one, exactly
    like an unfused program).

    With a ``quarantine`` set, a quarantined or raising chain rule is
    skipped the same way as in :func:`lower_instr` — the chain then
    executes link-by-link, each link taking its own (quarantine-aware)
    instruction lowering.  A rule returning a :class:`Decline` adds
    ``"rule: why"`` to ``declines``.
    """
    _ensure_registered()
    interpret = pallas_interpret(*_arrays(srcs))
    for rule in _CHAIN_RULES:
        if quarantine is not None:
            qkey = quarantine_key(rule.name, "chain", srcs[0])
            if qkey in quarantine:
                continue
        try:
            lowered = rule.lower(instrs, srcs, batch_dims, interpret,
                                 segment_bytes=segment_bytes)
        except Exception:
            if quarantine is None:
                raise
            quarantine.add(quarantine_key(rule.name, "chain", srcs[0]))
            continue
        if isinstance(lowered, Decline):
            if declines is not None:
                declines.append(f"{rule.name}: {lowered}")
            continue
        if lowered is not None:
            val, path, seg = lowered
            return val, Lowering(dst=instrs[-1].dst, opcode="chain",
                                 path=path, kernel=rule.name, segments=seg,
                                 launches=1, instrs=len(instrs))
    return None


def lower_xengine(direction: str, eqn_node, eqn_srcs: Sequence,
                  instrs: Sequence[TMInstr],
                  tm_srcs: Sequence[Sequence[jnp.ndarray | None]],
                  segment_bytes: int | None = None,
                  quarantine: set | None = None,
                  declines: list | None = None,
                  ) -> tuple[jnp.ndarray, Lowering] | None:
    """Lower a cross-engine crossing (compute eqn + adjacent TM chain)
    through the cross-engine registry.

    The returned record's ``dst`` is what the ONE launch produces: the
    chain's final dst for ``compute_to_tm`` (the eqn's output streams into
    the chain and never materializes), the eqn's output for
    ``tm_to_compute`` (the chain output streams into the eqn's input
    blocks).  ``launches=1`` and ``instrs=len(instrs)+1`` count the eqn, so
    launch/instruction accounting stays honest against the split path.
    Returns None when no rule claims the crossing — the caller then
    executes eqn and chain separately, bit-exact.  ``quarantine`` works as
    in :func:`lower_instr`: a raising rule is quarantined under its
    shape-class key and skipped on later runs; a :class:`Decline` adds
    ``"rule: why"`` to ``declines``."""
    _ensure_registered()
    interpret = pallas_interpret(*_arrays(eqn_srcs, tm_srcs))
    dst = (instrs[-1].dst if direction == "compute_to_tm"
           else eqn_node.dst_names[0])
    for rule in _XENGINE_RULES:
        if quarantine is not None:
            qkey = quarantine_key(rule.name, f"xchain.{direction}", eqn_srcs)
            if qkey in quarantine:
                continue
        try:
            hook = fault_hook
            if hook is not None:
                hook("lowering", f"{rule.name}:xchain:{dst}")
            lowered = rule.lower(direction, eqn_node, eqn_srcs, instrs,
                                 tm_srcs, interpret,
                                 segment_bytes=segment_bytes)
        except Exception:
            if quarantine is None:
                raise
            quarantine.add(quarantine_key(rule.name, f"xchain.{direction}",
                                          eqn_srcs))
            continue
        if isinstance(lowered, Decline):
            if declines is not None:
                declines.append(f"{rule.name}: {lowered}")
            continue
        if lowered is not None:
            val, path, seg = lowered
            return val, Lowering(dst=dst, opcode="xchain", path=path,
                                 kernel=rule.name, segments=seg,
                                 launches=1, instrs=len(instrs) + 1)
    return None
