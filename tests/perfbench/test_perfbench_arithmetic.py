"""The benchmark's own arithmetic, on synthetic data: whole-wave interval,
latency from due times, the trace reduction, the roofline's byte count."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import reduce, roofline, tracered, waves  # noqa: E402


def burst(t0, n, step=0.001):
    return [t0 + i * step for i in range(n)]


def test_group_waves_splits_on_the_gap_only():
    times = burst(1.0, 100) + burst(1.5, 50) + burst(1.5 + 0.049 + 0.04, 10)
    ws = waves.group_waves(times, 0.05)
    assert [w.pods for w in ws] == [100, 60]
    assert ws[0].t_first == 1.0 and ws[1].t_first == 1.5


def test_whole_wave_interval_snaps_both_edges_and_keeps_the_stall():
    # a wave straddles the opening (starts at 9.99), then waves every second
    # but for a 5 s stall after the one at 13; one wave straddles the close
    times = burst(9.99, 1000, 0.0001)                 # starts before t_open=10
    starts = [10.5, 11.5, 12.5, 13.0, 18.0, 19.0, 19.98]
    for s in starts:
        times += burst(s, 1024, 0.00002)
    times += burst(20.6, 512, 0.00002)                # the wave that ends it
    times += burst(21.5, 300, 0.00002)
    ws = waves.group_waves(times, 0.05)
    iv = waves.whole_wave_interval(ws, 10.0, 20.0)
    assert iv.t_start == 10.5                 # not the partial wave at 9.99
    assert iv.t_end == 20.6                   # first wave at or after the close
    assert iv.waves == len(starts) and iv.pods == 1024 * len(starts)
    # the stall is inside: the rate is all pods over all the time
    assert iv.pods / (iv.t_end - iv.t_start) == pytest.approx(7 * 1024 / 10.1)


@pytest.mark.parametrize("case", ["no_closing_wave", "nothing_inside"])
def test_whole_wave_interval_is_none_without_both_edges(case):
    if case == "no_closing_wave":
        ws = waves.group_waves(burst(10.5, 10) + burst(11.5, 10), 0.05)
    else:
        ws = waves.group_waves(burst(9.0, 10) + burst(20.5, 10), 0.05)
    assert waves.whole_wave_interval(ws, 10.0, 20.0) is None


def test_closing_wave_seen_needs_a_new_wave_after_the_close():
    straddle = burst(19.99, 400, 0.0001)      # began before 20.0, runs past it
    assert not waves.closing_wave_seen(straddle, 0.05, 20.0)
    assert waves.closing_wave_seen(straddle + [20.2], 0.05, 20.0)


def test_latency_counts_from_the_due_time_not_the_issue_time():
    # the generator ran 0.4 s late on the second pod; the third never bound
    rec = {
        "due": [("a", "p0", "measure", 10.00, 10.0), ("a", "p1", "measure", 11.40, 11.0),
                ("a", "p2", "measure", 12.00, 12.0)],
        "bound": {("a", "p0"): (10.2, "n0", 1), ("a", "p1"): (11.5, "n1", 2)},
        "t_drained": 80.0,
    }
    lat = reduce.latencies(rec)
    assert lat == pytest.approx([0.2, 0.5, 68.0])     # 11.5 - 11.0, not 11.5 - 11.4
    assert reduce.lateness(rec) == pytest.approx([0.0, 0.4, 0.0])
    assert reduce.percentile(lat, 50) == pytest.approx(0.5)


def test_percentile_interpolates():
    assert reduce.percentile([1, 2, 3, 4], 50) == pytest.approx(2.5)
    assert reduce.percentile([], 50) is None


# a small recorded trace in the reduction's plain form (ns): two device ops
# that overlap, a module line, and host spans on two threads
TRACE = {
    "/device:TPU:0": {
        "XLA Ops": [
            ("%fusion.1 = f32[8]{0} fusion(...)", 1_000.0, 400.0),
            ("%while.2 = (s32[]) while(...)", 1_200.0, 600.0),     # overlaps: union 1000..1800
            ("%fusion.1 = f32[8]{0} fusion(...)", 5_000.0, 100.0),
            ("%copy.3 = f32[8]{0} copy(...)", 9_500.0, 1_000.0),    # runs past the window
        ],
        "XLA Modules": [("jit_run_warm(123)", 1_000.0, 800.0), ("jit__unpack(7)", 5_000.0, 100.0)],
    },
    "/host:CPU": {
        "thread-a": [("perfbench_window", 0.0, 10_000.0), ("encode_dispatch", 100.0, 850.0)],
        "thread-b": [("commit", 1_900.0, 3_000.0), ("store_create", 5_200.0, 4_000.0),
                     ("unrelated", 0.0, 10_000.0)],
    },
}
SPANS = ("perfbench_window", "encode_dispatch", "commit", "store_create")


def test_trace_reduction_busy_union_and_gap_attribution():
    out = tracered.reduce(TRACE, SPANS)
    assert out["window_s"] == pytest.approx(10_000e-9)
    # union of 1000..1800, 5000..5100 and 9500..10000 (clipped at the window)
    assert out["busy_s"] == pytest.approx((800 + 100 + 500) * 1e-9)
    assert out["modules"]["jit_run_warm"] == [pytest.approx(800e-9), 1]
    ops = dict(out["device_ops"])
    assert ops["while.2"] == pytest.approx(600e-9) and ops["fusion.1"] == pytest.approx(500e-9)
    # gaps, longest first: 5100..9500 under store_create, 1800..5000 under
    # commit, 0..1000 under encode_dispatch
    assert [g[0] for g in out["idle_gaps"]] == ["store_create", "commit", "encode_dispatch"]
    assert out["idle_gaps"][0][1] == pytest.approx(4_400e-9)


def test_trace_reduction_without_a_device_plane_is_not_measured():
    assert tracered.reduce({"/host:CPU": TRACE["/host:CPU"]}, SPANS) is None


@pytest.mark.parametrize("shape,expect", [
    ((1024, 8192, 4), 4 * (8192 * 4 + 1024 * 4) + 8 * 1024 * 8192),
    ((64, 8192, 6), 4 * (8192 * 6 + 64 * 6) + 8 * 64 * 8192),
])
def test_roofline_bytes_are_a_function_of_the_shapes_alone(shape, expect):
    assert roofline.solve_min_bytes(*shape) == expect
    assert roofline.solve_min_seconds(*shape, 819e9) == pytest.approx(expect / 819e9)


def test_roofline_at_the_issues_shape_is_about_82_us():
    assert roofline.solve_min_seconds(1024, 8192, 4, 819e9) == pytest.approx(82.1e-6, rel=0.01)
