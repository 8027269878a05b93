"""The yolov3tiny cell runs end to end at 64x64 on the CPU, and the control
(the reference at three bfloat16 passes in the program's place) fails the
limit."""

import pytest

from bench.harness import Cell

from helpers import SEED, run_small


@pytest.mark.parametrize("workload", ["yolov3tiny-448.stream"])
def test_cell_runs_at_small_size(workload):
    out, lines = run_small(workload)
    assert out["correct"] is True, lines
    assert out["failed"] == 0 and out["attempted"] >= 2
    names = {m["name"] for m in Cell.load(workload).end_to_end}
    assert set(out["metrics"]) == names
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["checks"]["tail_mismatch"]["value"] == 0
    assert lines[-2].startswith("check grid_rel_err:")


def test_control_reads_above_the_limit():
    cell = Cell.load("yolov3tiny-448.stream")
    traffic = dict(cell.traffic, images=2)
    dep = cell.deployment_class()(cell.spec, traffic, SEED, small=True)
    dep.stop()
    assert dep.control_error() > cell.spec["limits"]["grid_rel_err"]


def test_baseline_times_every_class_through_the_server_and_plain_jit():
    from bench.baseline import time_classes
    cell = Cell.load("yolov3tiny-448.stream")
    traffic = dict(cell.traffic, images=2)
    dep = cell.deployment_class()(cell.spec, traffic, SEED, small=True)
    try:
        rows = time_classes(dep.server, dep.classes(), (1,), reps=2)
    finally:
        dep.stop()
    assert [r["class"] for r in rows] == [c[0] for c in dep.classes()]
    assert all(r["served_ms"] > 0 and r["jit_ms"] > 0 for r in rows)
