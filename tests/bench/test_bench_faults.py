"""A run with the timed path broken underneath comes out not correct: once
for each fault the cells can have.  The harness's look for a chip is
skipped (CPU, small size); everything else runs as on the chip."""

import jax.numpy as jnp
import pytest

from bench.harness import Cell
from helpers import run_small


@pytest.fixture
def broken_step(monkeypatch):
    """Wrap every served decode-layer step the program builds."""
    from repro.serving import decode

    def install(wrap):
        real = decode.make_layer_step

        def make(cfg, params, *, position):
            return wrap(real(cfg, params, position=position))

        monkeypatch.setattr(decode, "make_layer_step", make)
    return install


def test_token_altered_where_produced(broken_step):
    def wrap(step):
        def altered(tokens, ck, cv):
            logits, ck, cv = step(tokens, ck, cv)
            return logits.at[..., 7].add(1e4), ck, cv
        return altered
    broken_step(wrap)
    out, _ = run_small("phi4mini-l1.chat-p128-o16")
    assert out["correct"] is False
    assert out["checks"]["logit_rms_err"]["value"] > 1.0


def test_step_returns_its_state_unchanged(broken_step):
    def wrap(step):
        def stale(tokens, ck, cv):
            logits, _, _ = step(tokens, ck, cv)
            return logits, ck, cv
        return stale
    broken_step(wrap)
    out, _ = run_small("phi4mini-l1.chat-p128-o16")
    assert out["correct"] is False


def test_answer_altered_where_produced(monkeypatch):
    net = Cell.load("yolov3tiny-448.stream").module("configs",
                                                    "yolov3tiny-448")
    real = net.network

    def altered(params, img, spec):
        p1, p2 = real(params, img, spec)
        return p1, p2 + 0.01 * jnp.abs(p2).max()

    monkeypatch.setattr(net, "network", altered)
    out, _ = run_small("yolov3tiny-448.stream")
    assert out["correct"] is False
    assert out["checks"]["grid_rel_err"]["value"] > 1e-3


def test_degraded_path_comes_out_not_correct(monkeypatch):
    dep = Cell.load("yolov3tiny-448.stream").deployment_class()
    real = dep.audit

    def degraded(self):
        out = real(self)
        out["problems"].append("yolov3_tiny: phases fell down the backend "
                               "ladder {3: 'fused'}")
        return out

    monkeypatch.setattr(dep, "audit", degraded)
    out, lines = run_small("yolov3tiny-448.stream")
    assert out["correct"] is False
    assert out["checks"]["grid_rel_err"]["value"] <= \
        out["checks"]["grid_rel_err"]["limit"]
