"""Observability tests: tracer integrity, Chrome export, serving traces.

The acceptance bar: the tracer survives a 4-thread nesting soak with zero
integrity violations; the Chrome-trace export round-trips through
``json.loads`` with consistent timestamps; the no-op tracer records
nothing; and a traced TMServer run produces one ``phase/{index}/{kind}``
span per executed phase whose engine-track overlap agrees with
``ServerStats.overlap_ratio()``.  The clock anchor maps tracer spans onto a
live profile's clock; phase spans carry their group and the host's issue
stamp; a traced server records compiles and collections while it runs.
"""

from __future__ import annotations

import gc
import json
import pathlib
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import monitoring

from repro.compiler import tm_compile
from repro.obs import (CLOCK_ANCHOR, NULL_TRACER, HostHooks, NullTracer,
                       SpanRecord, Tracer, as_tracer, clock_anchor,
                       overlap_from_trace)
from repro.runtime.streams import StreamRuntime, overlap_from_events
from repro.serving import ServerConfig, ServerStats, TMServer
from repro.serving.decode import DecodeStats
from repro.serving.stats import _percentile, latency_percentiles


def _tm_fn(x):
    h = jnp.transpose(x, (0, 2, 1))
    h = h * 2.0
    h = jnp.flip(h, axis=1)
    return jnp.pad(h, ((0, 0), (1, 1), (0, 0)))


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------

def test_span_records_name_track_args_and_nesting():
    tr = Tracer()
    with tr.span("compile", track="t0"):
        with tr.span("compile/trace") as sp:   # inherits parent's track
            sp.set(summary="ok")
    spans = tr.spans()
    assert [s.name for s in spans] == ["compile/trace", "compile"]
    assert all(s.track == "t0" for s in spans)
    inner, outer = spans
    assert inner.depth == 1 and outer.depth == 0
    assert inner.arg("summary") == "ok"
    assert outer.t_start <= inner.t_start <= inner.t_end <= outer.t_end
    assert tr.spans(prefix="compile/") == [inner]
    assert tr.tracks() == ["t0"]


def test_add_span_and_counters():
    tr = Tracer(clock=time.monotonic)
    tr.add_span("phase/0/tmu", "tmu", 1.0, 2.0, ok=True)
    tr.count("hbm/bytes", 100.0)
    tr.count("hbm/bytes", 50.0)
    tr.counter("server/outstanding", 3.0, track="server")
    (s,) = tr.spans(track="tmu")
    assert s.duration_s == pytest.approx(1.0)
    assert s.arg("ok") is True
    assert tr.counters() == {"hbm/bytes": 150.0, "server/outstanding": 3.0}


def test_tracer_detail_validation():
    assert Tracer().detail == "phase"
    assert Tracer(detail="instr").detail == "instr"
    with pytest.raises(ValueError, match="unknown detail"):
        Tracer(detail="everything")


def test_as_tracer_normalization():
    assert as_tracer(None) is NULL_TRACER
    assert as_tracer(False) is NULL_TRACER
    fresh = as_tracer(True)
    assert isinstance(fresh, Tracer) and fresh is not NULL_TRACER
    tr = Tracer()
    assert as_tracer(tr) is tr


def test_null_tracer_records_nothing(tmp_path):
    tr = NullTracer()
    assert not tr.enabled and tr.detail == "phase"
    with tr.span("compile") as sp:
        sp.set(anything=1)
    tr.add_span("phase/0/tmu", "tmu", 0.0, 1.0)
    tr.instant("x")
    tr.count("c", 5)
    tr.counter("g", 2)
    assert tr.spans() == [] and tr.counters() == {} and tr.tracks() == []
    assert tr.nesting_errors() == []
    trace = tr.export_chrome_trace(str(tmp_path / "null.json"))
    assert trace["traceEvents"] == []


# ---------------------------------------------------------------------------
# integrity: multi-thread soak + overlap_ok
# ---------------------------------------------------------------------------

def test_four_thread_nesting_soak():
    tr = Tracer()
    n_threads, n_iters = 4, 200
    errors: list = []

    def worker(tid: int) -> None:
        try:
            for i in range(n_iters):
                with tr.span(f"outer/{tid}", track=f"w{tid}") as sp:
                    sp.set(i=i)
                    with tr.span("inner/a"):
                        pass
                    with tr.span("inner/b"):
                        tr.count(f"work/{tid}")
                # two threads share each ext track, so the windows have
                # concurrent lifetimes — the request-span shape
                tr.add_span(f"ext/{tid}", f"eng{tid % 2}",
                            tr._clock() - 1e-4, tr._clock(),
                            overlap_ok=True)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    spans = tr.spans()
    assert len(spans) == n_threads * n_iters * 4
    assert tr.nesting_errors() == []          # stack discipline + durations
    assert all(s.duration_s >= 0.0 for s in spans)
    for t in range(n_threads):
        assert len(tr.spans(track=f"w{t}")) == n_iters * 3
        assert tr.counters()[f"work/{t}"] == n_iters


def test_overlap_ok_exempt_from_stack_discipline():
    tr = Tracer()
    # two concurrent request windows on one track: legal only as overlap_ok
    tr.add_span("request/a", "requests", 0.0, 2.0, overlap_ok=True)
    tr.add_span("request/b", "requests", 1.0, 3.0, overlap_ok=True)
    assert tr.nesting_errors() == []
    tr.add_span("request/c", "requests", 2.5, 4.0)
    tr.add_span("request/d", "requests", 3.0, 5.0)
    assert any("partial overlap" in e for e in tr.nesting_errors())


def test_negative_duration_is_an_integrity_error():
    tr = Tracer()
    tr.add_span("bad", "t", 2.0, 1.0)
    assert any("negative duration" in e for e in tr.nesting_errors())


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_export_round_trip(tmp_path):
    tr = Tracer()
    with tr.span("compile", track="main"):
        with tr.span("compile/trace"):
            pass
    tr.add_span("phase/0/tmu", "tmu", tr.t0 + 0.001, tr.t0 + 0.002)
    tr.instant("submit", track="main", n=1)
    tr.count("tmu/launches", 3, track="counters")
    path = tmp_path / "trace.json"
    exported = tr.export_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == exported
    events = loaded["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"main", "tmu", "counters"} <= names
    # engines order first in the tid map
    tmu_meta = next(e for e in meta if e["args"]["name"] == "tmu")
    assert tmu_meta["tid"] == 0
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"compile", "compile/trace",
                                       "phase/0/tmu"}
    for e in xs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
    assert [e for e in events if e["ph"] == "i"][0]["name"] == "submit"
    c = [e for e in events if e["ph"] == "C"][0]
    assert c["name"] == "tmu/launches" and c["args"]["value"] == 3
    # events are time-sorted (metadata first at ts -1)
    ts = [e.get("ts", -1.0) for e in events]
    assert ts == sorted(ts)


def test_overlap_ok_spans_export_as_async_pairs():
    tr = Tracer()
    tr.add_span("request/f", "requests", tr.t0, tr.t0 + 0.5,
                overlap_ok=True, cold=True)
    tr.add_span("request/f", "requests", tr.t0 + 0.1, tr.t0 + 0.6,
                overlap_ok=True)
    events = tr.chrome_trace()["traceEvents"]
    begins = [e for e in events if e["ph"] == "b"]
    ends = [e for e in events if e["ph"] == "e"]
    assert len(begins) == 2 and len(ends) == 2
    assert {e["id"] for e in begins} == {e["id"] for e in ends}
    assert all(e["cat"] == "request" for e in begins + ends)
    assert begins[0]["args"]["cold"] is True
    assert not [e for e in events if e["ph"] == "X"]


# ---------------------------------------------------------------------------
# streams + serving integration
# ---------------------------------------------------------------------------

def test_stream_runtime_spans_match_event_overlap():
    tr = Tracer()
    with StreamRuntime(tracer=tr) as rt:
        ev_m = rt.submit("tmu", lambda: time.sleep(0.02), label="m0")
        rt.submit("tpu", lambda: time.sleep(0.02), label="t0")
        rt.submit("tmu", lambda: time.sleep(0.01), deps=[ev_m], label="m1")
        rt.synchronize(timeout=10.0)
        timeline = rt.timeline()
    # every realized event interval landed on its engine's track verbatim
    for engine in ("tmu", "tpu"):
        ev_ivs = sorted((e.t_start, e.t_end) for e in timeline
                        if e.engine == engine)
        sp_ivs = sorted((s.t_start, s.t_end) for s in tr.spans(track=engine))
        assert ev_ivs == sp_ivs
    from_trace = overlap_from_trace(tr)
    from_events = overlap_from_events(timeline)
    assert from_trace["overlap_ratio"] == \
        pytest.approx(from_events["overlap_ratio"], abs=1e-9)
    assert tr.nesting_errors() == []


def test_traced_server_phase_spans_and_overlap_agreement(rng):
    tr = Tracer()
    x = jnp.asarray(rng.rand(2, 8, 6).astype(np.float32))
    with TMServer(ServerConfig(max_batch=2, batch_timeout_s=0.001,
                               trace=tr)) as srv:
        for _ in range(3):
            futs = [srv.submit(_tm_fn, x, fn_key="tmfn") for _ in range(4)]
            for f in futs:
                np.testing.assert_array_equal(
                    np.asarray(f.result(timeout=120)),
                    np.asarray(_tm_fn(x)))
        stats_overlap = srv.stats.overlap_ratio()
        compiled = srv.cache.get(srv.cache.keys()[0]).compiled
    # one span per phase execution, named phase/{index}/{kind}
    for phase in compiled.partition_report.phases:
        spans = tr.spans(prefix=f"phase/{phase.index}/{phase.kind}")
        assert spans, f"phase {phase.index} executed without a span"
        assert all(s.track == phase.engine for s in spans)
    # request windows are concurrent-lifetime spans on the requests track
    reqs = tr.spans(track="requests")
    assert len(reqs) == 12 and all(s.overlap_ok for s in reqs)
    assert all(s.arg("ok") is True for s in reqs)
    # the trace and the stats reduce the SAME intervals: tight agreement
    assert overlap_from_trace(tr)["overlap_ratio"] == \
        pytest.approx(stats_overlap, abs=0.02)
    assert tr.nesting_errors() == []
    # served compiles are traced too
    assert tr.spans(prefix="compile/")
    counters = tr.counters()
    assert counters["cache/hits"] >= 1 and counters["cache/misses"] == 1


def test_instr_detail_records_per_instruction_spans(rng):
    tr = Tracer(detail="instr")
    x = jnp.asarray(rng.rand(2, 6, 4).astype(np.float32))
    compiled = tm_compile(_tm_fn, x, tracer=tr)
    out, _ = compiled.run(x, tracer=tr)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(_tm_fn(x)))
    assert tr.spans(prefix="phase/")
    assert tr.spans(prefix="instr/") or tr.spans(prefix="chain/")
    counters = tr.counters()
    assert counters.get("tmu/launches", 0) > 0
    assert tr.nesting_errors() == []


# ---------------------------------------------------------------------------
# the profiler's clock, groups, issue stamps, compiles and collections
# ---------------------------------------------------------------------------

def _profile_events(path: pathlib.Path, prefix: str) -> list:
    """(name, start_ns, end_ns, stats) of every host event under ``path``
    whose name starts with ``prefix``."""
    from jax.profiler import ProfileData
    (pb,) = sorted(path.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns),
                                dict(e.stats)))
    return out


def test_clock_anchor_maps_tracer_spans_onto_the_profile(tmp_path):
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        clock_anchor()
        for _ in range(3):
            with tr.span("probe"), jax.profiler.TraceAnnotation("probe"):
                time.sleep(0.005)
        clock_anchor()
    finally:
        jax.profiler.stop_trace()
    anchors = _profile_events(tmp_path, CLOCK_ANCHOR)
    assert len(anchors) == 2
    offsets = [s - st["monotonic_ns"] for _, s, _, st in anchors]
    # the two anchors agree on the offset: one clock, read twice
    assert abs(offsets[0] - offsets[1]) < 0.5e6
    probes = sorted(e[1:3] for e in _profile_events(tmp_path, "probe"))
    spans = sorted((s.t_start, s.t_end) for s in tr.spans(prefix="probe"))
    assert len(probes) == len(spans) == 3
    for (p0, p1), (t0, t1) in zip(probes, spans):
        assert abs(t0 * 1e9 + offsets[0] - p0) < 0.5e6
        assert abs(t1 * 1e9 + offsets[0] - p1) < 0.5e6


@pytest.mark.parametrize("scheduler", ["fifo", "continuous"])
def test_traced_phase_spans_carry_group_and_issue_stamp(rng, scheduler):
    tr = Tracer()
    x = jnp.asarray(rng.rand(2, 8, 6).astype(np.float32))
    with TMServer(ServerConfig(max_batch=2, batch_timeout_s=0.001,
                               scheduler=scheduler, trace=tr)) as srv:
        futs = [srv.submit(_tm_fn, x, fn_key="tmfn") for _ in range(6)]
        for f in futs:
            f.result(timeout=120)
        compiled = srv.cache.get(srv.cache.keys()[0]).compiled
    n_phases = len(compiled.partition_report.phases)
    by_group: dict = {}
    for s in tr.spans(prefix="phase/"):
        g = s.arg("group")
        assert isinstance(g, int)
        assert s.t_start <= s.arg("issued") <= s.t_end
        by_group.setdefault(g, []).append(s)
    # exactly one span per executed phase: each group ran every phase once
    assert by_group
    for spans in by_group.values():
        assert sorted(s.name for s in spans) == sorted(
            f"phase/{p.index}/{p.kind}"
            for p in compiled.partition_report.phases)
        assert len(spans) == n_phases
    reqs = tr.spans(prefix="request/")
    assert len(reqs) == 6
    assert {r.arg("group") for r in reqs} == set(by_group)
    for r in reqs:
        # a request's group ran inside its submit -> respond window
        phases = by_group[r.arg("group")]
        assert r.t_start <= min(s.t_start for s in phases)
        assert max(s.t_end for s in phases) <= r.t_end
    assert tr.nesting_errors() == []


def test_untraced_server_installs_no_hooks():
    srv = TMServer(ServerConfig()).start()
    try:
        assert srv._hooks is None and srv.tracer is NULL_TRACER
        assert srv(_tm_fn, jnp.ones((2, 4, 3), jnp.float32)).shape == \
            (2, 5, 4)
    finally:
        srv.stop()
    assert not any(isinstance(getattr(cb, "__self__", None), HostHooks)
                   for cb in gc.callbacks)


def test_traced_server_records_compiles_and_collections():
    tr = Tracer()
    srv = TMServer(ServerConfig(trace=tr)).start()
    try:
        hooks = srv._hooks
        assert hooks.installed
        assert hooks._on_gc in gc.callbacks
        assert hooks._on_duration in monitoring.get_event_duration_listeners()
        # a function no cache has seen: trace, lower and compile all run
        salt = time.monotonic_ns() % 997 + 3.0
        jax.jit(lambda v: v * salt + 1.0)(jnp.ones((3,), jnp.float32))
        gc.collect()
    finally:
        srv.stop()
    names = {s.name for s in tr.spans(prefix="jax/")}
    assert {"jax/trace", "jax/lower", "jax/compile"} <= names
    for s in tr.spans(prefix="jax/"):
        assert s.t_start <= s.t_end
        assert s.track == threading.current_thread().name
    collections = tr.spans(prefix="host/gc")
    assert any(s.arg("generation") == 2 for s in collections)
    # stop() removes both hooks: nothing more is recorded
    assert not hooks.installed
    assert hooks._on_gc not in gc.callbacks
    assert hooks._on_duration not in monitoring.get_event_duration_listeners()
    n = len(tr.spans())
    jax.jit(lambda v: v - salt)(jnp.ones((3,), jnp.float32))
    gc.collect()
    assert len(tr.spans()) == n


class _ReentryLock:
    """A plain lock that counts, instead of deadlocking on, an acquire by
    the thread that already holds it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._owner = None
        self._depth = 0
        self.reentries = 0

    def __enter__(self):
        me = threading.get_ident()
        if self._owner == me:
            self.reentries += 1
        else:
            self._lock.acquire()
            self._owner = me
        self._depth += 1
        return self

    def __exit__(self, *exc):
        self._depth -= 1
        if self._depth == 0:
            self._owner = None
            self._lock.release()
        return False


def test_collections_inside_the_tracers_lock_do_not_deadlock():
    # a collection can start inside any of the tracer's critical sections;
    # the gc hook runs on that thread and must not take the lock again
    tr = Tracer()
    tr._lock = lock = _ReentryLock()
    x = jnp.ones((2, 8, 6), jnp.float32)
    srv = TMServer(ServerConfig(max_batch=2, batch_timeout_s=0.001,
                                trace=tr)).start()
    errors = []

    def soak():
        try:
            for _ in range(40):
                srv.submit(_tm_fn, x, fn_key="tmfn").result(timeout=60)
                tr.spans(prefix="request/")
        except Exception as e:          # noqa: BLE001 - reported below
            errors.append(e)

    try:
        srv(_tm_fn, x, fn_key="tmfn")   # compiled before the soak
        threshold = gc.get_threshold()
        gc.set_threshold(1)
        try:
            t = threading.Thread(target=soak, daemon=True)
            t.start()
            t.join(timeout=120)
        finally:
            gc.set_threshold(*threshold)
        assert not t.is_alive(), "submits did not finish"
    finally:
        srv.stop()
    assert errors == []
    assert lock.reentries == 0
    assert len(tr.spans(prefix="host/gc")) > 40
    assert tr.nesting_errors() == []


def test_servers_sharing_a_tracer_share_its_hooks():
    tr = Tracer()
    a = TMServer(ServerConfig(trace=tr)).start()
    b = TMServer(ServerConfig(trace=tr)).start()
    try:
        assert a._hooks is b._hooks is tr.host_hooks
        # one hook into this tracer, however many servers share it, so a
        # collection is recorded once
        assert sum(getattr(getattr(cb, "__self__", None), "tracer", None)
                   is tr for cb in gc.callbacks) == 1
        gc.collect()
        assert any(s.arg("generation") == 2
                   for s in tr.spans(prefix="host/gc"))
        a.stop()
        a.stop()                        # a second stop drops no user
        assert b._hooks.installed
    finally:
        a.stop()
        b.stop()
    assert not tr.host_hooks.installed
    assert tr.host_hooks._on_gc not in gc.callbacks


def test_compile_span_names_the_span_open_on_its_thread():
    tr = Tracer()
    srv = TMServer(ServerConfig(trace=tr)).start()
    try:
        salt = time.monotonic_ns() % 991 + 5.0
        with tr.span("admit/probe"):
            jax.jit(lambda v: v + salt)(jnp.ones((2,), jnp.float32))
        tr.set_running("phase/0/tpu")
        try:
            jax.jit(lambda v: v * salt)(jnp.ones((2,), jnp.float32))
        finally:
            tr.set_running(None)
    finally:
        srv.stop()
    within = {s.arg("within") for s in tr.spans(prefix="jax/compile")}
    assert {"admit/probe", "phase/0/tpu"} <= within
    assert tr.current() is None


# ---------------------------------------------------------------------------
# stats satellites: percentiles + interval window
# ---------------------------------------------------------------------------

def test_percentile_linear_interpolation():
    xs = [1.0, 2.0, 3.0, 4.0]
    assert _percentile(xs, 0.0) == 1.0
    assert _percentile(xs, 1.0) == 4.0
    assert _percentile(xs, 0.5) == pytest.approx(2.5)   # not nearest-rank
    assert _percentile(xs, 0.25) == pytest.approx(1.75)
    assert _percentile([], 0.5) == 0.0
    assert _percentile([7.0], 0.99) == 7.0
    xs100 = [float(i) for i in range(1, 101)]
    assert _percentile(xs100, 0.99) == pytest.approx(np.percentile(xs100, 99))
    assert _percentile(xs100, 0.99) < 100.0             # p99 != max


def test_latency_percentiles_shape():
    out = latency_percentiles([0.3, 0.1, 0.2], "warm_latency")
    assert set(out) == {"warm_latency_p50_s", "warm_latency_p95_s",
                        "warm_latency_p99_s"}
    assert out["warm_latency_p50_s"] == pytest.approx(0.2)


def test_server_stats_snapshot_percentile_keys():
    st = ServerStats()
    for v in (0.1, 0.2, 0.3):
        st.record_done(v, cold=False)
    snap = st.snapshot()
    for q in (50, 95, 99):
        assert f"warm_latency_p{q}_s" in snap
        assert f"cold_latency_p{q}_s" in snap
    assert snap["warm_latency_p50_s"] == pytest.approx(0.2)


def test_recent_intervals_window_and_dropped_counter():
    st = ServerStats(recent_intervals=4)
    for i in range(6):
        st.record_interval("tmu", float(i), float(i) + 0.5)
    assert st.dropped_intervals == 2        # window of 4, 6 inserts
    assert st.snapshot()["dropped_intervals"] == 2
    st2 = ServerStats()                     # default window absorbs all
    for i in range(6):
        st2.record_interval("tmu", float(i), float(i) + 0.5)
    assert st2.dropped_intervals == 0


def test_decode_stats_snapshot_percentile_keys():
    ds = DecodeStats()
    ds.prefill_latency_s.extend([0.5, 0.7])
    ds.step_latency_s.extend([0.01, 0.02, 0.03])
    snap = ds.snapshot()
    for q in (50, 95, 99):
        assert f"step_latency_p{q}_s" in snap
        assert f"prefill_latency_p{q}_s" in snap
    assert snap["step_latency_p50_s"] == pytest.approx(0.02)
