"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Device work is read from each TPU plane's ``XLA Ops`` line: one event per
executed HLO op, on the same clock as the host's ``TraceAnnotation`` spans.
The benchmark marks its measured window with a ``bench/window`` annotation
and its own calls with ``bench/sleep``, ``bench/submit``, ``bench/wait`` and
``bench/step``; everything is clipped to the window.

On a TPU each op event is named by its HLO text, ``%name.N = type op(...)``.
Naming rule for the TMU kernels: the program's Pallas kernels reach XLA as
Mosaic custom calls, whose HLO text carries
``custom_call_target="tpu_custom_call"``.  Every other device op is an XLA
compute op.  Ops are grouped by their HLO name with the ``%`` and the
numeric suffix dropped (``%fusion.2`` → ``fusion``; a Pallas kernel keeps
its kernel function's name, such as ``_run``).
"""

from __future__ import annotations

import collections
import pathlib
import re

from bench.stats import gaps, union_length

WINDOW = "bench/window"
# what the host was doing in a device idle gap, most telling first
_HOST_LABELS = ("bench/step", "bench/wait", "bench/submit", "bench/sleep")
_SUFFIX = re.compile(r"[._]\d+$")
_TM = 'custom_call_target="tpu_custom_call"'


def load(path):
    """The ProfileData of ``path`` (a ``.xplane.pb`` or a profile dir —
    the newest trace under it)."""
    from jax.profiler import ProfileData
    p = pathlib.Path(path)
    if p.is_dir():
        found = sorted(p.rglob("*.xplane.pb"), key=lambda f: f.stat().st_mtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {p}")
        p = found[-1]
    return ProfileData.from_file(str(p))


def is_tm_kernel(text: str) -> bool:
    """True for a Mosaic (Pallas) kernel event; see the module docstring."""
    return _TM in text


def op_name(text: str) -> str:
    """An op event's group: its HLO name without ``%`` and suffix."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def device_ops(pd) -> dict[str, list]:
    """``{device plane: [(op name, start_ns, end_ns, is_tm), ...]}``."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        evs = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                evs.append((op_name(e.name), float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            is_tm_kernel(e.name)))
        if evs:
            out[plane.name] = evs
    return out


def host_spans(pd, prefix: str = "bench/") -> list[tuple[str, float, float]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    s = float(e.start_ns)
                    out.append((e.name, s, s + float(e.duration_ns)))
    return out


def _label(t0: float, t1: float, spans) -> str:
    """The benchmark call in progress over most of a device idle gap."""
    cover = collections.Counter()
    for name, s, e in spans:
        if name != WINDOW and s < t1 and e > t0:
            cover[name] += min(e, t1) - max(s, t0)
    for name in _HOST_LABELS:
        if cover.get(name, 0.0) >= 0.5 * (t1 - t0):
            return name
    return max(cover, key=cover.get) if cover else "none"


def reduce(pd, chips: int = 1, top: int = 10) -> dict | None:
    """Device busy and idle time, TM-kernel and XLA device seconds, the
    busiest ops and the longest idle gaps inside the ``bench/window``
    annotation.  None when the trace holds no window or no device op."""
    spans = host_spans(pd)
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    ops = device_ops(pd)
    if not windows or not ops:
        return None
    w0, w1 = windows[0]
    busy, tm_ns, xla_ns = 0.0, 0.0, 0.0
    by_name: collections.Counter = collections.Counter()
    first = None
    for plane in sorted(ops):
        evs = ops[plane]
        busy += union_length([(s, e) for _, s, e, _ in evs], w0, w1)
        for name, s, e, tm in evs:
            d = max(0.0, min(e, w1) - max(s, w0))
            if not d:
                continue
            by_name[name] += d
            if tm:
                tm_ns += d
            else:
                xla_ns += d
        if first is None:
            first = evs
    n = max(chips, 1)
    idle = gaps([(s, e) for _, s, e, _ in first], w0, w1)
    idle.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n * 1e-9,
        "tm_s": tm_ns / n * 1e-9,
        "xla_s": xla_ns / n * 1e-9,
        "device_ops": [[k, v * 1e-9] for k, v in by_name.most_common(top)],
        "idle_gaps": [[_label(s, e, spans), (e - s) * 1e-9]
                      for s, e in idle[:top]],
    }

