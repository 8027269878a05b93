"""chip_smoke.py at smoke size on the CPU: its two phases, its
no-degradation audit, and its refusal to run anywhere but a TPU."""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _quiet(*_):
    pass


def test_decode_phase_at_smoke_size(smoke):
    from repro.configs.phi4_mini_3p8b import smoke_config
    s = smoke.decode_phase(smoke_config(), batch=2, prompt_len=8, max_len=16,
                           steps=3, log=_quiet)
    # prefill + 2 decode positions, each its own compile-cache entry
    assert s["entries"] == 3 and s["cache_misses"] == 3
    assert any(p.startswith("pallas.") for p in s["paths"])
    assert s["paths"].get("xla", 0) > 0
    # f32 smoke config: fused phases round differently from eager dispatch
    assert s["logit_max_abs_err"] < 1e-4
    assert len(s["request_s"]) == 3


def test_cnn_phase_at_smoke_size(smoke):
    s = smoke.cnn_phase(image=64, n_requests=4, max_batch=2, log=_quiet)
    paths = s["paths"]
    assert "pallas.chain+rme.evaluate" in paths   # the detect tail
    assert any(p in paths for p in ("pallas.chain+route", "pallas.route"))
    assert s["cache_hits"] >= 2                   # the second batch of each
    assert len(s["request_s"]) == 8


def test_audit_fails_on_quarantine_and_ladder(smoke):
    from repro.models import cnn
    from repro.serving import ServerConfig, TMServer
    x = jnp.ones((1, 4, 4, 128), jnp.float32)
    skip = jnp.ones((1, 8, 8, 128), jnp.float32)
    with TMServer(ServerConfig(backend="pallas", max_batch=1)) as srv:
        srv(cnn.yolo_neck, x, skip, fn_key="neck")
        assert smoke.audit(srv)["entries"] == 1
        entry = srv.cache.entries()[0]
        entry.quarantine.add(("tm_affine", "coarse", ((1,),)))
        with pytest.raises(smoke.SmokeFailure, match="quarantined"):
            smoke.audit(srv)
        entry.quarantine.clear()
        entry.degraded_phases[0] = "fused"
        with pytest.raises(smoke.SmokeFailure, match="backend ladder"):
            smoke.audit(srv)


def test_main_refuses_a_non_tpu_device(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main() != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "needs a TPU" in err
