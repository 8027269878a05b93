"""The reduction from the server's spans to dispatch, handoff, compile and
served-idle numbers (``bench/progtrace.py``), on hand-built spans with
known answers, on a live CPU profile, and through a small traced run; and
the existing device-trace reduction, unchanged on its recorded trace."""

import math
import types

import pytest

import jax

from bench import devtrace, progtrace
from bench.harness import ROOT, RunContext
from bench.loops import Record
from repro.obs import SpanRecord, Tracer, clock_anchor

from helpers import SEED, SMALL_TRAFFIC

DATA = ROOT / "tests" / "bench" / "data"
OFFSET_NS = 5e12          # the profile clock minus the tracer clock


def _phase(name, t0, issued, t1, group):
    return SpanRecord(name, "tmu" if name.endswith("tmu") else "tpu", t0, t1,
                      args=(("ok", True), ("issued", issued),
                            ("group", group)))


def _span(name, t0, t1, track="t", **args):
    return SpanRecord(name, track, t0, t1, args=tuple(args.items()))


# two groups; group 0 runs two phases with a gap between them, group 1 one
SPANS = [
    _phase("phase/0/tmu", 1.0, 1.1, 1.3, 0),
    _phase("phase/1/tpu", 1.5, 1.6, 2.0, 0),
    _phase("phase/0/tmu", 3.0, 3.2, 3.4, 1),
    _span("request/f", 0.9, 2.2, "requests", group=0),
    _span("request/f", 0.95, 2.2, "requests", group=0),
    _span("request/f", 2.8, 3.6, "requests", group=1),
    # a trace with a nested trace inside it, then the backend compile
    _span("jax/trace", 1.05, 1.25),
    _span("jax/trace", 1.1, 1.2),
    _span("jax/compile", 1.25, 1.28),
    _span("jax/lower", 10.0, 11.0),        # after the window
    _span("host/gc", 1.62, 1.64, generation=2),
    _span("admit/f", 0.92, 0.99),
]
# device op intervals, on the profile's clock
BUSY_S = [(1.15, 1.3), (1.7, 2.0), (3.25, 3.4)]


def _ctx(spans, n_events=2, lo=0.0, hi=5.0, flops=None):
    rec = Record(index=0, t_due=0.0,
                 events=[lo + (hi - lo) * (k + 1) / (n_events + 1)
                         for k in range(n_events)], t_done=hi)
    ctx = RunContext(records=[rec], t0=lo, t_close=hi, seconds=hi - lo,
                     setup_s=1.0, event_flops=flops or (lambda i, k: 0.0),
                     peak_flops=1.0)
    if spans is not None:
        ctx.spans = spans
    return ctx


def test_interval_arithmetic():
    a = [(0.0, 2.0), (3.0, 5.0)]
    b = [(1.0, 3.5), (4.0, 4.5)]
    assert progtrace.intersect(a, b) == [(1.0, 2.0), (3.0, 3.5), (4.0, 4.5)]
    assert progtrace.subtract(a, b) == [(0.0, 1.0), (3.5, 4.0), (4.5, 5.0)]
    assert progtrace.subtract(a, []) == a
    assert progtrace.clip([(2.0, 0.5), (-1.0, 1.0), (4.0, 9.0)], 0.0, 5.0) \
        == [(0.0, 1.0), (4.0, 5.0)]
    assert progtrace.length(a) == 4.0


def test_readers_on_known_spans():
    ctx = _ctx(SPANS)
    # issue: 0.1 + 0.1 + 0.2 s over two tokens
    assert progtrace.dispatch_ms(ctx) == pytest.approx(200.0)
    # group 0: 1.2 s in flight, phases cover 0.8; group 1: 0.6 and 0.4
    assert progtrace.handoff_ms(ctx) == pytest.approx(300.0)
    # [1.05, 1.28]: the nested trace counts once, the late lowering not
    assert progtrace.compile_ms(ctx) == pytest.approx(115.0)
    assert progtrace.groups(SPANS)[0][0] == (1.0, 2.2)


def test_window_clips_every_reader():
    ctx = _ctx(SPANS, n_events=1, lo=1.2, hi=1.7)
    # issue [1.5, 1.6] only; handoff [1.3, 1.5]; compile [1.2, 1.28]
    assert progtrace.dispatch_ms(ctx) == pytest.approx(100.0)
    assert progtrace.handoff_ms(ctx) == pytest.approx(200.0)
    assert progtrace.compile_ms(ctx) == pytest.approx(80.0)


def test_served_idle_and_its_split_by_host_activity():
    busy = [(s * 1e9 + OFFSET_NS, e * 1e9 + OFFSET_NS) for s, e in BUSY_S]
    r = progtrace.served_idle(SPANS, busy, OFFSET_NS, 0.0, 5.0)
    assert r["inflight_s"] == pytest.approx(1.8)
    assert r["idle_s"] == pytest.approx(1.2)
    split = r["split_s"]
    assert list(split) == list(progtrace.ACTIVITIES)
    assert split["host/gc"] == pytest.approx(0.02)
    assert split["jax"] == pytest.approx(0.10)
    assert split["issue"] == pytest.approx(0.35)
    assert split["device_wait"] == pytest.approx(0.13)
    assert split["handoff"] == pytest.approx(0.60)
    assert sum(split.values()) == pytest.approx(r["idle_s"])
    # the same intervals seen through a wrong offset are no longer idle
    # where the device ran
    shifted = progtrace.served_idle(SPANS, busy, OFFSET_NS + 1e9, 0.0, 5.0)
    assert shifted["idle_s"] != pytest.approx(1.2)


def test_identity_residual_is_the_groups_own_phase_overlap():
    spans = [
        _phase("phase/0/tmu", 0.0, 0.1, 0.4, 7),
        _phase("phase/1/tpu", 0.2, 0.3, 0.6, 7),   # 0.2 s beside phase 0
        _span("request/g", 0.0, 1.0, "requests", group=7),
    ]
    r = progtrace.identity(spans)
    # 0.4 + 0.4 summed + 0.4 handoff = 1.2 against 1.0 in flight
    assert r["groups"] == 1
    assert r["residual_share"] == pytest.approx(0.2)
    assert progtrace.identity(SPANS)["residual_share"] == pytest.approx(0.0)


def test_readers_read_nothing_from_a_program_without_issue_stamps():
    old = [SpanRecord("phase/0/tmu", "tmu", 1.0, 1.3, args=(("ok", True),)),
           SpanRecord("request/f", "requests", 0.9, 2.0, overlap_ok=True),
           _span("compile/trace", 0.1, 0.2)]
    ctx = _ctx(old)
    assert progtrace.dispatch_ms(ctx) is None
    assert progtrace.handoff_ms(ctx) is None
    assert progtrace.compile_ms(ctx) is None
    assert progtrace.served_idle(old, [], 0.0, 0.0, 5.0) is None
    assert progtrace.dispatch_ms(_ctx([])) is None
    # spans but no token in the window: nothing per token
    assert progtrace.dispatch_ms(_ctx(SPANS, n_events=0)) is None


def test_readers_find_the_deployments_tracer():
    tracer = Tracer()
    for s in SPANS:
        tracer.add_span(s.name, s.track, s.t_start, s.t_end,
                        overlap_ok=s.overlap_ok, **dict(s.args))

    class Dep:
        def tracer(self):
            return tracer

        def event_flops(self, i, k):
            return 0.0

    ctx = _ctx(None, flops=Dep().event_flops)
    assert progtrace.dispatch_ms(ctx) == pytest.approx(200.0)
    assert progtrace.handoff_ms(ctx) == pytest.approx(300.0)
    # a context that reaches no deployment reads nothing
    assert progtrace.dispatch_ms(_ctx(None)) is None


def test_clock_offsets_from_a_live_profile(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        clock_anchor()
        jax.numpy.ones(8).block_until_ready()
        clock_anchor()
    finally:
        jax.profiler.stop_trace()
    offsets = progtrace.clock_offsets(devtrace.load(tmp_path))
    assert len(offsets) == 2
    assert abs(offsets[1] - offsets[0]) < 0.5e6      # under 0.5 ms apart


def test_device_trace_reduction_is_unchanged():
    """``devtrace.reduce`` reads the recorded chip trace as it always has."""
    r = devtrace.reduce(devtrace.load(DATA / "fixture.xplane.pb"))
    assert r["window_s"] == pytest.approx(0.021554989, rel=1e-9)
    assert r["busy_s"] == pytest.approx(9.514e-06, rel=1e-9)
    assert r["tm_s"] == pytest.approx(2.836e-06, rel=1e-9)
    assert r["xla_s"] == pytest.approx(6.678e-06, rel=1e-9)
    assert r["device_ops"] == [["fusion", pytest.approx(6.678e-06)],
                               ["_lambda_", pytest.approx(2.836e-06)]]
    assert [g[0] for g in r["idle_gaps"]] == ["bench/sleep"] * 5
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [0.007869634, 0.00623742, 0.005894763, 0.000855419, 0.000688239])
    # and the same trace holds no clock anchor: nothing to map onto it
    assert progtrace.clock_offsets(
        devtrace.load(DATA / "fixture.xplane.pb")) == []


def test_traced_small_run_reports_the_new_metrics():
    workload = "yolov3tiny-448.stream"
    lines = []
    out = progtrace.run_traced(workload, SEED, 1.5, allow_cpu=True,
                               small=True, traffic=SMALL_TRAFFIC[workload],
                               log=lines.append)
    assert out["correct"] is True, lines
    m = out["metrics"]
    for name in ("dispatch_ms.stream", "handoff_ms.stream",
                 "compile_ms.stream", "served_idle_ms.stream"):
        assert math.isfinite(m[name]["value"]) and m[name]["value"] >= 0
    assert m["dispatch_ms.stream"]["value"] > 0
    extra = out["progtrace"]
    assert len(extra["clock_offsets_ns"]) == 2
    assert abs(extra["clock_drift_ms"]) < 0.5
    assert extra["identity"]["groups"] >= 1
    idle = extra["served_idle"]
    assert sum(idle["split_s"].values()) == pytest.approx(idle["idle_s"])
    assert any(s.startswith("served idle by host activity:") for s in lines)
    assert list(out)[-1] == "checks"


def test_tracer_only_run_reports_end_to_end_metrics():
    workload = "yolov3tiny-448.stream"
    out = progtrace.run_tracer_only(workload, SEED, 1.5, allow_cpu=True,
                                    small=True,
                                    traffic=SMALL_TRAFFIC[workload],
                                    log=lambda s: None)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"image_p95_ms", "setup_s"}
    assert "breakdown" not in out


def test_spans_of_caches_the_deployments_spans():
    calls = []

    class Dep:
        def tracer(self):
            calls.append(1)
            return types.SimpleNamespace(spans=lambda: list(SPANS))

        def event_flops(self, i, k):
            return 0.0

    ctx = _ctx(None, flops=Dep().event_flops)
    progtrace.dispatch_ms(ctx)
    progtrace.compile_ms(ctx)
    assert len(calls) == 1


@pytest.mark.parametrize("workload", ["phi4mini-l1.chat-p128-o16",
                                      "yolov3tiny-448.stream"])
def test_spans_of_finds_each_real_deployments_tracer(workload):
    # the readers reach the server's tracer through the bound event_flops
    # the harness hands over; a harness that wrapped it would silently
    # drop dispatch_ms, handoff_ms and compile_ms from the result line
    import inspect
    from bench import harness
    tr = Tracer()
    for s in SPANS:
        tr.add_span(s.name, s.track, s.t_start, s.t_end, **dict(s.args))
    dep = object.__new__(harness.Cell.load(workload).deployment_class())
    dep.server = types.SimpleNamespace(tracer=tr)
    assert "event_flops=dep.event_flops" in inspect.getsource(harness.run)
    ctx = _ctx(None, flops=dep.event_flops)
    assert progtrace.spans_of(ctx) == tr.spans()
    assert progtrace.dispatch_ms(ctx) == pytest.approx(200.0)

