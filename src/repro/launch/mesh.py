"""Production meshes + per-cell sharding rules.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is an
outer data-parallel axis whose gradient all-reduce crosses the (slower)
pod interconnect — the axis gradient compression targets.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_test_mesh(*, multi_pod: bool = False):
    """Reduced mesh for CI-scale dry-run tests (8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def rules_for_cell(kind: str, *, long_context: bool = False,
                   batch_is_sharded: bool = True) -> dict:
    """Logical-axis rules per shape kind (see runtime.sharding.DEFAULT_RULES).

    train    — DP batch over (pod, data); TP heads/mlp/vocab/experts over
               model; SP activation seq over model; FSDP weights over data.
    prefill  — same as train minus FSDP-on-master (no optimizer state).
    decode   — seq axis is 1: no SP; batch over (pod, data).
    long     — batch=1: KV-cache/attention sequence over data instead
               (flash-decode-style distributed attention).
    """
    rules = {
        "batch": ("pod", "data") if batch_is_sharded else None,
        "seq": ("model",) if kind in ("train", "prefill") else None,
        # decode: KV-cache sequence sharded over the model axis -> GSPMD
        # emits the distributed flash-decode pattern (partial softmax +
        # tiny psums); long-context (batch=1) shards it over data instead.
        "kv_seq": (("data",) if long_context else ("model",))
        if kind == "decode" else None,
        "embed": None,
        "embed_fsdp": ("data",) if kind == "train" else None,
        "heads": ("model",),
        "kv_heads": None,
        "head_dim": None,
        "mlp": ("model",),
        "vocab": ("model",),
        "experts": ("model",),
        "expert_mlp": None,
        "layers": None,
        "state": None,
        "conv": None,
        "cap": None,
    }
    if long_context:
        rules["batch"] = None
    return rules


def specialize_rules(rules: dict, cfg, mesh) -> dict:
    """Arch-aware rule fixes for divisibility.

    MoE expert parallelism needs num_experts % model_size == 0 (llama4: 16
    experts over model=16).  When it does not divide (qwen2: 60 experts),
    fall back to tensor parallelism *within* each expert: experts
    replicated, expert hidden dim sharded over model."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model = sizes.get("model", 1)
    rules = dict(rules)
    if getattr(cfg, "family", None) == "moe":
        if cfg.num_experts_padded % model:
            rules["experts"] = None
            rules["expert_mlp"] = ("model",)
        # §Perf hillclimb B2: sequence parallelism conflicts with token
        # dispatch (the per-sequence gather needs the full local sequence),
        # costing an extra all-gather per MoE layer per direction.  Measured
        # to win for high-expert-count archs (qwen2: E=60, small d_model)
        # and to LOSE for llama4 (E=16, d5120 — the SP savings on its large
        # dense-attention activations outweigh the dispatch gathers), so it
        # is opt-in per arch.
        if rules.get("seq") and getattr(cfg, "moe_drop_sp", False):
            rules["seq"] = None
    return rules
