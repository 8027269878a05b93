"""One run of one cell: build, warm, measure, check, report.

Everything particular to a configuration, a traffic mix or a metric lives in
a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` — the configuration's sizes as run, its
  source, its cuts and its limits; ``bench/configs/<config>.py`` builds the
  deployment (``Deployment``); ``bench/configs/<config>.reference.py`` is its
  plain reference, which imports nothing of the program;
* ``bench/traffic/<mix>.json`` — the mix's parameters and the loop that
  offers it (``bench/loops/<loop>.py``);
* ``bench/metrics/<metric>.py`` — one metric's reader: ``read(ctx)``
  returns a number, or None when there is nothing to read.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import logging
import math
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """The cell's chips are not there; nothing was measured."""


def load_module(path: pathlib.Path):
    """Import a file by path (names may hold ``-`` and ``.``)."""
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(ROOT)))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with everything it names loaded."""

    name: str
    chips: int
    config: dict
    spec: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def load(cls, workload: str, root: pathlib.Path = ROOT) -> "Cell":
        bench = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
        w = cells[workload]
        config = next(c for c in bench["configs"] if c["name"] == w["config"])

        def mine(m):
            return workload in m.get("workloads", [workload])

        return cls(name=workload, chips=int(w["chips"]), config=config,
                   spec=json.loads((root / config["file"]).read_text()),
                   traffic=json.loads((root / "bench" / "traffic"
                                       / f"{w['traffic']}.json").read_text()),
                   end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                   per_layer=[m for m in bench["per_layer"] if mine(m)])

    def module(self, kind: str, name: str):
        return load_module(ROOT / "bench" / kind / f"{name}.py")

    def deployment_class(self):
        return self.module("configs", self.config["name"]).Deployment

    def loop(self):
        return self.module("loops", self.traffic["loop"])


def device_line(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu" and not allow_cpu:
        raise NoChip(f"needs a TPU, found {d.platform} ({d.device_kind})")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int):
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter(logging.Handler):
    """Counts JAX lowerings and backend compiles through jax.monitoring, so
    a compile inside the measured window shows, and names what compiled
    while ``naming`` is set (from JAX's own compile log record)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.lowerings = 0
        self.backend_compiles = 0
        self.naming = False
        self.names: collections.Counter = collections.Counter()
        self.first: dict = {}

    def install(self) -> "CompileCounter":
        import jax
        from jax._src import dispatch
        events = {dispatch.JAXPR_TO_MLIR_MODULE_EVENT: "lowerings",
                  dispatch.BACKEND_COMPILE_EVENT: "backend_compiles"}

        def listen(event, _secs, **_):
            attr = events.get(event)
            if attr:
                setattr(self, attr, getattr(self, attr) + 1)

        self._listen = listen
        jax.monitoring.register_event_duration_secs_listener(listen)
        log = logging.getLogger("jax._src.interpreters.pxla")
        self._level = log.level
        log.setLevel(logging.DEBUG)
        log.addHandler(self)
        return self

    def uninstall(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.removeHandler(self)
        log.setLevel(self._level)

    def emit(self, record) -> None:
        if self.naming and str(record.msg).startswith("Compiling ") \
                and record.args:
            name = str(record.args[0])
            self.names[name] += 1
            self.first.setdefault(name, record.getMessage()[:400])

    def snapshot(self) -> tuple[int, int]:
        return self.lowerings, self.backend_compiles


@dataclasses.dataclass
class RunContext:
    """What the metric readers read."""

    records: list
    t0: float
    t_close: float
    seconds: float
    setup_s: float
    event_flops: object            # (index, k) -> model FLOPs of event k
    peak_flops: float
    queue_delays: list = dataclasses.field(default_factory=list)
    inflight: list = dataclasses.field(default_factory=list)
    dev: dict | None = None

    def window_events(self):
        """(record, k, t) of every token or answer that reached the host
        inside the window."""
        for r in self.records:
            for k, t in enumerate(r.events):
                if self.t0 <= t <= self.t_close:
                    yield r, k, t


def inflight_intervals(tracer) -> list[tuple[float, float]]:
    """Per request, first phase start of its group -> results ready, from
    the server's ``request/`` and ``phase/`` spans (one clock: monotonic).
    A group's first phase start is the first phase span starting after the
    request was submitted."""
    import bisect
    starts = sorted(s.t_start for s in tracer.spans(prefix="phase/"))
    out = []
    for r in tracer.spans(prefix="request/"):
        i = bisect.bisect_left(starts, r.t_start)
        if i < len(starts) and starts[i] <= r.t_end:
            out.append((starts[i], r.t_end))
    return out


def _num(x):
    """A metric value as JSON takes it: inf (a tail that fell on a failed
    request) becomes null."""
    if x is None or (isinstance(x, float) and not math.isfinite(x)):
        return None
    return x


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, allow_cpu: bool = False, small: bool = False,
        traffic: dict | None = None, log=None) -> dict:
    """Run ``workload`` once and return the result line's object.  Raises
    :class:`NoChip` before any work where the chips are missing.  The tests
    run it on the CPU at a small size (``allow_cpu``, ``small``, and
    ``traffic`` entries that replace the mix's)."""
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    cell = Cell.load(workload)
    cell.traffic.update(traffic or {})
    device = device_line(cell.chips, allow_cpu=allow_cpu)
    import jax
    from repro.platform import enable_compile_cache
    from bench.peaks import peaks
    peak = (peaks(device["kind"]) if device["platform"] == "tpu"
            else {"flops_bf16": math.nan})
    if device["platform"] == "tpu":
        log(f"device: {device}; compile cache: {enable_compile_cache()}")
    compiles = CompileCounter().install()
    dep = trace_dir = None
    try:
        dep = cell.deployment_class()(cell.spec, cell.traffic, seed,
                                      small=small, trace=trace)
        dep.warm()
        setup_s = time.monotonic() - t_process
        log(f"setup_s {setup_s:.3f}")
        dep.reset_series()
        before = compiles.snapshot()
        compiles.naming = True
        misses_before = dep.cache_misses()
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench-trace-",
                                         dir=str(ROOT / ".bench_out"))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans: TraceMe only
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            res = cell.loop().run(dep, cell.traffic, seed, seconds)
        finally:
            if trace:
                jax.profiler.stop_trace()
        after = compiles.snapshot()
        compiles.naming = False
        misses = dep.cache_misses() - misses_before
        device["memory_peak_bytes"] = memory_peak_bytes(cell.chips)
        audit = dep.audit()
        ctx = RunContext(records=res.records, t0=res.t0,
                         t_close=res.t_close, seconds=seconds,
                         setup_s=setup_s, event_flops=dep.event_flops,
                         peak_flops=peak["flops_bf16"])
        if trace:
            ctx.queue_delays = dep.queue_delays()
            ctx.inflight = inflight_intervals(dep.tracer())
    finally:
        if dep is not None:
            dep.stop()
        compiles.uninstall()
    breakdown = None
    if trace:
        from bench import devtrace
        t = time.monotonic()
        ctx.dev = devtrace.reduce(devtrace.load(trace_dir), chips=cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.monotonic() - t:.1f} s")
        if ctx.dev is not None:
            device["busy_s"] = ctx.dev["busy_s"]
            device["window_s"] = ctx.dev["window_s"]
            breakdown = {"device_ops": ctx.dev["device_ops"],
                         "idle_gaps": ctx.dev["idle_gaps"]}
    gc.collect()

    # -- correctness: the served answers against the plain reference --------
    t = time.monotonic()
    checks = dep.check([r for r in res.records if r.ok], seed)
    log(f"reference check took {time.monotonic() - t:.1f} s")
    failed = sum(not r.ok for r in res.records)
    # a degraded lowering, a quarantine or a fallback down the backend
    # ladder times another path than the one configured: not correct
    correct = (failed == 0 and res.unfinished == 0 and not audit["problems"]
               and all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values()))

    # -- metrics ------------------------------------------------------------
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = cell.module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": _num(value), "unit": m["unit"]}

    # -- diagnostics, then the numbers compared, last on stderr --------------
    late = sorted(res.late_s) or [0.0]
    n_events = sum(len(r.events) for r in res.records)
    log(f"generator lateness: max {late[-1] * 1e3:.3f} ms, "
        f"p95 {late[int(0.95 * (len(late) - 1))] * 1e3:.3f} ms over "
        f"{len(res.late_s)} hand-offs")
    log(f"samples: {len(res.records)} requests due in the window, "
        f"{failed} failed, {res.unfinished} unfinished, {n_events} "
        f"tokens/answers")
    log(f"compiles inside the window: {after[0] - before[0]} lowerings, "
        f"{after[1] - before[1]} backend compiles, {misses} server cache "
        f"misses; most compiled: {compiles.names.most_common(8)}")
    for name, msg in compiles.first.items():
        log(f"compiled inside the window: {msg}")
    for k, v in audit.items():
        log(f"audit {k}: {v}")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    out = {"correct": bool(correct), "attempted": len(res.records),
           "failed": failed + res.unfinished, "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
