"""The phi4mini cells run end to end at smoke widths on the CPU, and the
control (the float8 reference in the program's place) fails the limit."""

import math

import numpy as np
import pytest

from bench.harness import Cell

from helpers import SEED, run_small


@pytest.mark.parametrize("workload", ["phi4mini-l1.chat-p128-o16"])
def test_cell_runs_at_small_size(workload):
    out, lines = run_small(workload)
    assert out["correct"] is True, lines
    assert out["failed"] == 0 and out["attempted"] >= 2
    names = {m["name"] for m in Cell.load(workload).end_to_end}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "checks"
    err = out["checks"]["logit_rms_err"]
    assert err["value"] <= err["limit"]
    # the numbers compared come last on stderr, each beside its limit
    assert lines[-1].startswith("check logit_rms_err:")
    assert any("0 lowerings, 0 backend compiles, 0 server cache misses" in s
               for s in lines)


def test_control_reads_above_the_limit():
    cell = Cell.load("phi4mini-l1.chat-p128-o16")
    traffic = dict(cell.traffic, prompt_len=8, output_len=3)
    dep = cell.deployment_class()(cell.spec, traffic, SEED, small=True)
    dep.stop()
    recs = dep.reference_greedy(4)
    control = float(dep.reference_errors(recs, quantized=True).max())
    assert math.isfinite(control)
    assert control > cell.spec["limits"]["logit_rms_err"]


def test_error_is_in_sigmas_of_the_reference_row():
    from bench.harness import ROOT, load_module
    ref = load_module(ROOT / "bench" / "configs" / "phi4mini-l1.reference.py")
    want = np.array([[0.0, 1.0, 3.0, 2.0]])
    assert ref.rms_err_sigma(want, want)[0] == 0.0
    got = want + np.array([[0.0, 0.0, 0.0, 2.0]])
    assert ref.rms_err_sigma(got, want)[0] == pytest.approx(
        1.0 / want.std())
