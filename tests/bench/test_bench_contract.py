"""BENCHMARK.json against the files it names, the command's refusal to run
without a TPU, and the trace reduction on a small recorded trace."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import devtrace
from bench.harness import ROOT, load_module

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
DATA = ROOT / "tests" / "bench" / "data"


def test_every_name_resolves_to_its_own_file():
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert (ROOT / "bench" / "configs" / f"{c['name']}.py").is_file()
        assert (ROOT / "bench" / "configs"
                / f"{c['name']}.reference.py").is_file()
        spec = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(spec["reduced"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] == 1
        traffic = json.loads((ROOT / "bench" / "traffic"
                              / f"{w['traffic']}.json").read_text())
        assert (ROOT / "bench" / "loops" / f"{traffic['loop']}.py").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"])
        if m["name"] == "setup_s":
            continue
        mod = load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py")
        assert callable(mod.read)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in BENCH["workloads"]:
        def mine(m):
            return w["name"] in m.get("workloads", [w["name"]])
        e2e = [m["name"] for m in BENCH["end_to_end"] if mine(m)]
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = [m for m in BENCH["per_layer"] if mine(m)]
        assert layers
        assert all(m["moves"] in e2e for m in layers)


def test_the_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_reduction_of_a_recorded_chip_trace():
    pd = devtrace.load(DATA / "fixture.xplane.pb")
    r = devtrace.reduce(pd)
    assert r is not None
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["tm_s"] > 0 and r["xla_s"] > 0
    assert r["tm_s"] + r["xla_s"] == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["device_ops"] and len(r["idle_gaps"]) <= 10
    labels = {g[0] for g in r["idle_gaps"]}
    assert labels <= {"bench/wait", "bench/sleep", "none"}
    assert "bench/sleep" in labels
