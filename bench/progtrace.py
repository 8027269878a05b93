"""Where a served token's host time goes, from the server's own spans.

A traced ``TMServer`` (``repro.obs``) records, on ``time.monotonic()``:

* ``phase/{i}/{kind}`` on the engine tracks, one span per executed phase:
  ``t_start`` → arg ``issued`` is the host issuing the phase's work,
  ``issued`` → ``t_end`` the wait for the device; arg ``group`` names the
  admitted group;
* ``request/{fn_key}``: submit → respond of each request, arg ``group``;
* ``jax/trace``, ``jax/lower``, ``jax/compile`` (JAX's own stages) and
  ``host/gc`` (garbage collections), on the thread they ran on.

A group is in flight from its first phase start to its requests' response.
This module reduces those spans to host seconds inside the window
``[lo, hi]``:

* dispatch — Σ (``issued`` − ``t_start``) over phase spans;
* handoff — per group, its in-flight interval minus the union of its
  phase spans (passing work between threads, waiting on a stream);
* compile — the union of the ``jax/*`` spans (nested traces once);

and, with a device profile whose ``obs/clock`` anchors
(``repro.obs.clock_anchor``) map the tracer's clock onto the profile's,

* served idle — device idle time inside the union of in-flight intervals,
  and its split by what the host was doing (:data:`ACTIVITIES`).

A tracer whose phase spans carry no ``issued`` comes from a program that
records none of these: every reader then returns None.  Run as a module
from the repo root, it runs one traced cell through ``bench.harness`` and
adds the served-idle numbers, which need the device profile, to the result
line::

    python3 -m bench.progtrace --workload <cell> --seed <n> --seconds <s>

and with ``--tracer-only`` runs the cell untraced by the profiler but with
the server's tracer on, for the tracer's overhead on the end-to-end
metrics.
"""

from __future__ import annotations

import contextlib
import math

from bench import devtrace
from bench.stats import merge, union_length

# the annotation repro.obs.clock_anchor() writes; named here so that the
# readers import nothing of the program
CLOCK = "obs/clock"
# what the host was doing during a device idle moment, innermost first
ACTIVITIES = ("host/gc", "jax", "issue", "device_wait", "handoff")


# -- interval arithmetic on sorted, disjoint lists ---------------------------

def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in merge(intervals)
            if min(e, hi) > max(s, lo)]


def intersect(a, b) -> list[tuple[float, float]]:
    """``a ∩ b`` of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b) -> list[tuple[float, float]]:
    """``a − b`` of two merged interval lists."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


# -- spans -------------------------------------------------------------------

def phase_spans(spans) -> list | None:
    """The phase spans that carry the host's issue stamp (None when the
    program stamps none)."""
    out = [s for s in spans if s.name.startswith("phase/")
           and s.arg("issued") is not None]
    return out or None


def groups(spans) -> dict:
    """``{group: (in-flight (start, end), [phase spans])}`` of every group
    whose requests responded."""
    phases: dict = {}
    for s in phase_spans(spans) or ():
        g = s.arg("group")
        if g is not None:
            phases.setdefault(g, []).append(s)
    ends: dict = {}
    for s in spans:
        g = s.arg("group") if s.name.startswith("request/") else None
        if g in phases:
            ends[g] = max(ends.get(g, -math.inf), s.t_end)
    return {g: ((min(p.t_start for p in phases[g]), ends[g]), phases[g])
            for g in ends}


def dispatch_s(spans, lo: float, hi: float) -> float | None:
    ph = phase_spans(spans)
    if ph is None:
        return None
    return sum(length(clip([(s.t_start, s.arg("issued"))], lo, hi))
               for s in ph)


def handoff_s(spans, lo: float, hi: float) -> float | None:
    if phase_spans(spans) is None:
        return None
    total = 0.0
    for (s, e), ph in groups(spans).values():
        inflight = clip([(s, e)], lo, hi)
        covered = intersect(merge((p.t_start, p.t_end) for p in ph),
                            inflight)
        total += length(inflight) - length(covered)
    return total


def compile_s(spans, lo: float, hi: float) -> float | None:
    if phase_spans(spans) is None:
        return None
    return union_length([(s.t_start, s.t_end) for s in spans
                         if s.name.startswith("jax/")], lo, hi)


def identity(spans, lo: float = -math.inf, hi: float = math.inf) -> dict:
    """Per group, in flight ≈ Σ phase issue + Σ device wait + handoff.  The
    residual is the time the group's own phases ran at once (counted twice
    by the sums), as a share of its in-flight time."""
    shares, tot_res, tot_in = [], 0.0, 0.0
    for (s, e), ph in groups(spans).values():
        if not lo <= s <= hi:
            continue
        inflight = e - s
        covered = length(merge((p.t_start, p.t_end) for p in ph))
        summed = sum(p.t_end - p.t_start for p in ph)
        res = summed + (inflight - covered) - inflight
        tot_res += res
        tot_in += inflight
        if inflight > 0:
            shares.append(res / inflight)
    return {"groups": len(shares),
            "residual_share": tot_res / tot_in if tot_in else None,
            "max_group_residual_share": max(shares) if shares else None}


# -- the device profile -----------------------------------------------------

def clock_offsets(pd) -> list[float]:
    """``start_ns − monotonic_ns`` of every ``obs/clock`` anchor, in time
    order: add one to a tracer time in ns to land on the profile's clock."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == CLOCK:
                    stats = {k: v for k, v in e.stats}
                    if "monotonic_ns" in stats:
                        out.append((float(e.start_ns),
                                    float(e.start_ns)
                                    - float(stats["monotonic_ns"])))
    return [off for _, off in sorted(out)]


def _to_ns(intervals, offset_ns: float) -> list[tuple[float, float]]:
    return [(s * 1e9 + offset_ns, e * 1e9 + offset_ns) for s, e in intervals]


def served_idle(spans, busy_ns, offset_ns: float, lo: float,
                hi: float) -> dict | None:
    """Device idle seconds inside the union of the groups' in-flight
    intervals, and their split by :data:`ACTIVITIES`: each idle moment goes
    to the first activity in that order that covers it on the host.
    ``busy_ns``: the device's op intervals on the profile's clock."""
    ph = phase_spans(spans)
    if ph is None:
        return None
    inflight = merge(_to_ns(clip([iv for iv, _ in groups(spans).values()],
                                 lo, hi), offset_ns))
    idle = subtract(inflight, merge(busy_ns))
    total = length(idle)
    cover = {
        "host/gc": [(s.t_start, s.t_end) for s in spans
                    if s.name == "host/gc"],
        "jax": [(s.t_start, s.t_end) for s in spans
                if s.name.startswith("jax/")],
        "issue": [(s.t_start, s.arg("issued")) for s in ph],
        "device_wait": [(s.arg("issued"), s.t_end) for s in ph],
    }
    split = {}
    for name in ACTIVITIES[:-1]:
        part = merge(_to_ns(cover[name], offset_ns))
        split[name] = length(intersect(idle, part)) * 1e-9
        idle = subtract(idle, part)
    split["handoff"] = length(idle) * 1e-9
    return {"inflight_s": length(inflight) * 1e-9, "idle_s": total * 1e-9,
            "split_s": split}


def busy_intervals(pd) -> list[tuple[float, float]]:
    """The first device plane's op intervals (ns, the profile's clock)."""
    ops = devtrace.device_ops(pd)
    if not ops:
        return []
    return [(s, e) for _, s, e, _ in ops[sorted(ops)[0]]]


# -- metric readers ----------------------------------------------------------

def spans_of(ctx) -> list:
    """The traced server's spans: ``ctx.spans`` where the run context has
    them, else the tracer of the deployment whose ``event_flops`` the
    context holds.  Empty when neither is there.

    Temporary: the fallback goes once ``bench/harness.py`` gives
    ``RunContext.spans`` (PERF.md, Open questions), together with
    :func:`run_traced`.  Until then
    ``test_spans_of_finds_each_real_deployments_tracer`` fails if the
    harness stops handing the deployment's own ``event_flops`` over."""
    spans = getattr(ctx, "spans", None)
    if spans is not None:
        return spans
    cached = ctx.__dict__.get("_progtrace_spans")
    if cached is None:
        dep = getattr(ctx.event_flops, "__self__", None)
        tracer = getattr(dep, "tracer", None)
        cached = list(tracer().spans()) if callable(tracer) else []
        ctx.__dict__["_progtrace_spans"] = cached
    return cached


def _per_unit_ms(ctx, seconds):
    n = sum(1 for _ in ctx.window_events())
    if seconds is None or not n:
        return None
    return seconds * 1e3 / n


def dispatch_ms(ctx):
    """Host issue time of the phases per token or answer."""
    return _per_unit_ms(ctx, dispatch_s(spans_of(ctx), ctx.t0, ctx.t_close))


def handoff_ms(ctx):
    """In-flight time outside any of the group's phases per token or
    answer."""
    return _per_unit_ms(ctx, handoff_s(spans_of(ctx), ctx.t0, ctx.t_close))


def compile_ms(ctx):
    """JAX trace, lowering and compile time per token or answer."""
    return _per_unit_ms(ctx, compile_s(spans_of(ctx), ctx.t0, ctx.t_close))


# -- the script ---------------------------------------------------------------

@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def run_traced(workload: str, seed: int, seconds: float, *, log=None,
               **kw) -> dict:
    """One ``--trace 1`` run of ``workload`` through ``bench.harness``,
    with a clock anchor at each end of the profile, and the result line
    extended by ``progtrace``: served idle and its split, the anchors'
    offsets, and the per-group identity.

    Temporary: it patches ``jax.profiler``, ``devtrace.load`` and
    ``harness.RunContext`` for the run, standing in for the harness edit
    PERF.md's Open questions name (anchors around the profile, the profile
    loaded once, ``RunContext.spans``); it goes with that edit."""
    import sys
    import time

    import jax
    from bench import harness
    from repro.obs import clock_anchor
    log = log or (lambda s: print(s, file=sys.stderr, flush=True))
    kept: dict = {}
    start, stop, load = (jax.profiler.start_trace, jax.profiler.stop_trace,
                         devtrace.load)

    def start_trace(*a, **k):
        start(*a, **k)
        clock_anchor()

    def stop_trace():
        clock_anchor()
        stop()

    def keep_load(path):
        kept["pd"] = load(path)
        return kept["pd"]

    class Context(harness.RunContext):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            kept["ctx"] = self

    with contextlib.ExitStack() as st:
        st.enter_context(_patched(jax.profiler, "start_trace", start_trace))
        st.enter_context(_patched(jax.profiler, "stop_trace", stop_trace))
        st.enter_context(_patched(devtrace, "load", keep_load))
        st.enter_context(_patched(harness, "RunContext", Context))
        out = harness.run(workload, seed, seconds, True,
                          t_process=kw.pop("t_process", time.monotonic()),
                          log=log, **kw)
    ctx, pd = kept["ctx"], kept.get("pd")
    spans = spans_of(ctx)
    offsets = clock_offsets(pd) if pd is not None else []
    # the cell's metric suffix, as its dispatch_ms metric names it
    suffix = next((m["name"].split(".", 1)[1]
                   for m in harness.Cell.load(workload).per_layer
                   if m["name"].startswith("dispatch_ms.")), workload)
    extra = {"clock_offsets_ns": offsets,
             "identity": identity(spans, ctx.t0, ctx.t_close)}
    if len(offsets) >= 2:
        extra["clock_drift_ms"] = (offsets[-1] - offsets[0]) * 1e-6
        r = served_idle(spans, busy_intervals(pd),
                        sum(offsets) / len(offsets), ctx.t0, ctx.t_close)
        if r is not None:
            extra["served_idle"] = r
            value = _per_unit_ms(ctx, r["idle_s"])
            out["metrics"][f"served_idle_ms.{suffix}"] = {"value": value,
                                                          "unit": "ms"}
            parts = ", ".join(f"{k} {v:.3f} s" for k, v in
                              r["split_s"].items())
            log(f"served idle by host activity: {r['idle_s']:.3f} s of "
                f"{r['inflight_s']:.3f} s in flight: {parts}")
    checks = out.pop("checks")
    out["progtrace"] = extra
    out["checks"] = checks
    return out


def run_tracer_only(workload: str, seed: int, seconds: float, **kw) -> dict:
    """One untraced run (end-to-end metrics, no profile) with the server's
    tracer on: the tracer's cost on the end-to-end metrics."""
    import time
    from bench import harness
    cls = harness.Cell.deployment_class

    def traced_class(cell):
        dep = cls(cell)
        return lambda *a, **k: dep(*a, **dict(k, trace=True))

    with _patched(harness.Cell, "deployment_class", traced_class):
        return harness.run(workload, seed, seconds, False,
                           t_process=kw.pop("t_process", time.monotonic()),
                           **kw)


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time
    t_process = time.monotonic()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    # the TPU runtime would otherwise log under a fixed path in /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--tracer-only", action="store_true")
    a = p.parse_args(argv)
    os.makedirs(os.path.join(root, ".bench_out"), exist_ok=True)
    from bench import harness
    try:
        if a.tracer_only:
            out = run_tracer_only(a.workload, a.seed, a.seconds,
                                  t_process=t_process)
        else:
            out = run_traced(a.workload, a.seed, a.seconds,
                             t_process=t_process)
    except harness.NoChip as e:
        print(f"progtrace: {e}; nothing was run", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
