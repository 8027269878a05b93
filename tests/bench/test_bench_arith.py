"""The benchmark's arithmetic on the CPU: percentiles over all samples,
interval unions, spreads, seeded schedules with due-time stamping, the
configurations' FLOP and byte counts against hand counts, the peaks table,
and the end-to-end and per-layer readers."""

import math
import statistics
import time
import types

import pytest

from bench import readers, schedule, stats
from bench.harness import ROOT, RunContext, inflight_intervals, load_module
from bench.loops import Record
from bench.peaks import peaks


def test_percentile_is_over_all_samples_with_linear_interpolation():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    # a failed request is inf and misses any tail it falls in
    assert stats.percentile([1.0] * 19 + [math.inf], 95) == math.inf
    assert stats.percentile([1.0] * 99 + [math.inf], 95) == 1.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_union_of_intervals_and_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (5.0, 5.0)]
    assert stats.merge(iv) == [(0.0, 2.0), (3.0, 4.0)]
    assert stats.union_length(iv) == 3.0
    assert stats.union_length(iv, 1.5, 3.5) == 1.0
    assert stats.gaps(iv, -1.0, 4.5) == [(-1.0, 0.0), (2.0, 3.0), (4.0, 4.5)]


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 11.0, 9.0, 10.5, 9.5, 10.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / med)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3])
def test_schedule_is_reproducible_and_every_seed_gets_the_same_gaps(seed):
    a = schedule.arrivals(4.0, 51.0, seed)
    assert a == schedule.arrivals(4.0, 51.0, seed)
    assert len(a) == 204 and a[0] == 0.0 and a[-1] < 51.0
    assert a != schedule.arrivals(4.0, 51.0, seed + 1)
    g = schedule.gaps(4.0, 51.0, seed)
    assert sorted(g) == sorted(schedule.gaps(4.0, 51.0, seed + 1))
    assert sum(g) == pytest.approx(51.0)


def test_pacer_stamps_due_times_and_lateness():
    t0 = time.monotonic() + 0.02
    p = schedule.Pacer(t0)
    due = p.wait_until(0.01)
    assert due == t0 + 0.01 and time.monotonic() >= due
    time.sleep(0.03)
    assert p.wait_until(0.0) == t0     # late: handed off at once
    assert p.late_s[1] >= 0.02


def test_peaks_table_knows_v5e_and_refuses_unknown_kinds():
    v5e = peaks("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def _cfg(name):
    import json
    return (load_module(ROOT / "bench" / "configs" / f"{name}.py"),
            json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                       .read_text()))


def test_phi4mini_flops_and_bytes_match_hand_counts():
    mod, spec = _cfg("phi4mini-l1")
    layer = (3072 * 5120 + 3072 * 3072 + 3072 * 16384 + 8192 * 3072)
    unembed = 3072 * 200064
    # one decode token at position 128 sees 129 keys
    attn = 2 * 24 * 128 * 129
    assert mod.step_flops(spec, 1, 129, 1) == 2 * (layer + unembed + attn)
    # the 1024-token prefill: ~1.47 TFLOP, logits at every position
    pre = mod.step_flops(spec, 1024, 1024, 1024)
    assert pre == 2 * (1024 * layer + 1024 * unembed
                       + 2 * 24 * 128 * 1024 * 1025 // 2)
    assert 1.46e12 < pre < 1.48e12
    weights = (layer + unembed) * 2 + 3 * 3072 * 4
    assert mod.step_bytes(spec, 4, 130) == weights + 4 * 2 * 130 * 8 * 128 * 2


def test_yolov3tiny_flops_and_bytes_match_hand_counts():
    mod, spec = _cfg("yolov3tiny-448")
    # darknet's yolov3-tiny.cfg at 448: (output size, kernel, in, out)
    convs = [(448, 3, 3, 16), (224, 3, 16, 32), (112, 3, 32, 64),
             (56, 3, 64, 128), (28, 3, 128, 256), (14, 3, 256, 512),
             (14, 3, 512, 1024), (14, 1, 1024, 256), (14, 3, 256, 512),
             (14, 1, 512, 255), (14, 1, 256, 128), (28, 3, 384, 256),
             (28, 1, 256, 255)]
    hand = sum(2 * s * s * k * k * ci * co for s, k, ci, co in convs)
    assert mod.image_flops(spec) == hand
    # darknet reports 5.571 BFLOPs at 416; the same network at 448
    assert hand == pytest.approx(5.571e9 * (448 / 416) ** 2, rel=0.01)
    weights = sum(k * k * ci * co + co for _, k, ci, co in convs) * 4
    io = (448 * 448 * 3 + (14 * 14 + 28 * 28) * 255) * 4
    assert mod.image_bytes(spec, 2) == weights + 2 * io


def _ctx(records, **kw):
    return RunContext(records=records, t0=0.0, t_close=10.0, seconds=10.0,
                      setup_s=1.0, event_flops=lambda i, k: 1e12,
                      peak_flops=100e12, **kw)


def test_end_to_end_readers_time_from_due_and_count_failures():
    recs = [Record(index=i, t_due=float(i), events=[i + 0.1, i + 0.3],
                   t_done=i + 0.3) for i in range(9)]
    recs.append(Record(index=9, t_due=9.0, error="boom"))
    ctx = _ctx(recs)
    # 10 samples, one inf: p95 lies between the last finite and the inf
    assert readers.answer_ms(ctx) == math.inf
    assert readers.token_gap_ms(ctx) == math.inf
    assert readers.token_gap_ms(_ctx(recs[:9])) == pytest.approx(200.0)
    assert readers.answer_ms(_ctx(recs[:9])) == pytest.approx(300.0)


def test_per_layer_readers():
    recs = [Record(index=0, t_due=0.0, events=[1.0, 2.0, 11.0], t_done=11.0)]
    ctx = _ctx(recs, queue_delays=[0.001, 0.003],
               inflight=[(0.5, 1.0), (1.5, 2.0), (1.8, 2.5)])
    assert readers.queue_wait_ms(ctx) == pytest.approx(2.0)
    # two events in the window, 1e12 FLOPs each, over 100 TFLOP/s x 1.5 s
    assert readers.mfu(ctx) == pytest.approx(100 * 2e12 / (100e12 * 1.5))
    assert readers.tm_kernel_ms(ctx) is None       # no trace: nothing read
    ctx.dev = {"tm_s": 0.02, "xla_s": 0.04, "busy_s": 0.06,
               "window_s": 0.2}
    assert readers.tm_kernel_ms(ctx) == pytest.approx(10.0)
    assert readers.xla_ms(ctx) == pytest.approx(20.0)
    assert readers.idle_share(ctx) == pytest.approx(70.0)
    assert readers.mfu(_ctx(recs)) is None         # no in-flight interval


def test_inflight_intervals_start_at_the_groups_first_phase():
    S = types.SimpleNamespace
    tracer = S(spans=lambda prefix: {
        "phase/": [S(t_start=1.2), S(t_start=1.5), S(t_start=3.1)],
        "request/": [S(t_start=1.0, t_end=2.0), S(t_start=3.0, t_end=4.0),
                     S(t_start=5.0, t_end=6.0)]}[prefix])
    assert inflight_intervals(tracer) == [(1.2, 2.0), (3.1, 4.0)]
