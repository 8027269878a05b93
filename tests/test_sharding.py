"""Sharding rules resolution + spec trees (single-device execution)."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P

from repro.launch.mesh import rules_for_cell, specialize_rules
from repro.runtime.sharding import (DEFAULT_RULES, shard, spec_of,
                                    tree_sharding, use_rules)


def _mesh1():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def test_spec_resolution_outside_context_is_noop():
    assert spec_of(("batch", "seq", "embed")) == P()
    x = jnp.ones((4, 4))
    assert shard(x, ("batch", None)) is x


def test_spec_resolution_in_context():
    with use_rules(_mesh1()):
        assert spec_of(("batch", None, "mlp")) == P("data", None, "model")
        assert spec_of((None, "embed")) == P(None, None)


def test_pod_axis_dropped_on_single_pod_mesh():
    with use_rules(_mesh1()):  # batch maps to ("pod","data") -> ("data",)
        assert spec_of(("batch",)) == P("data")


def test_tree_sharding_handles_none_and_tuples():
    mesh = _mesh1()
    specs = {"a": ("batch", "mlp"), "b": None, "c": {"d": (None, "vocab")}}
    sh = tree_sharding(specs, mesh)
    assert sh["a"].spec == P("data", "model")
    assert sh["b"].spec == P()
    assert sh["c"]["d"].spec == P(None, "model")


def test_rules_for_cell_kinds():
    tr = rules_for_cell("train")
    assert tr["embed_fsdp"] == ("data",) and tr["seq"] == ("model",)
    de = rules_for_cell("decode")
    assert de["seq"] is None and de["embed_fsdp"] is None
    lg = rules_for_cell("decode", long_context=True)
    assert lg["kv_seq"] == ("data",) and lg["batch"] is None


def test_specialize_rules_moe_divisibility():
    import dataclasses
    from repro.configs import get_config

    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)

    # qwen2's 60 experts are padded to 64 (EP divisibility, §Perf B1)
    qwen = get_config("qwen2-moe-a2.7b")
    assert qwen.num_experts_padded == 64
    r = specialize_rules(rules_for_cell("train"), qwen, FakeMesh)
    assert r["experts"] == ("model",)
    assert r["seq"] is None  # §Perf B2: no SP around MoE dispatch
    # without padding the rules fall back to TP-within-expert
    qwen_unpadded = dataclasses.replace(qwen, moe_pad_experts=0)
    r0 = specialize_rules(rules_for_cell("train"), qwen_unpadded, FakeMesh)
    assert r0["experts"] is None and r0["expert_mlp"] == ("model",)
    llama = get_config("llama4-scout-17b-a16e")  # 16 experts: divides
    r2 = specialize_rules(rules_for_cell("train"), llama, FakeMesh)
    assert r2["experts"] == ("model",)


def test_sharded_execution_single_device_matches_unsharded():
    """with_sharding_constraint annotations don't change values."""
    from repro.models.transformer import ModelConfig, init_lm, lm_loss
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      dtype=jnp.float32, remat="none")
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    plain, _ = lm_loss(cfg, params, toks, toks)
    with use_rules(_mesh1()):
        inside, _ = jax.jit(lambda p: lm_loss(cfg, p, toks, toks))(params)
    assert np.allclose(float(plain), float(inside), rtol=1e-6)
