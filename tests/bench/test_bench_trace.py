"""The reduction from a trace to busy time, idle gaps and op totals, on a
small trace written out by hand: two chips, a window, host spans."""
import pytest

from bench_small import ROOT  # noqa: F401  (puts the benchmark on the path)

MS = 1e6                                   # nanoseconds


def events():
    host = "/host:CPU"
    return [
        (host, "python", "window", 0 * MS, 100 * MS),
        (host, "python", "instant_ckpt", 10 * MS, 50 * MS),
        (host, "python", "fabric_model", 60 * MS, 30 * MS),
        (host, "python", "before_window", -50 * MS, 20 * MS),
        ("/device:TPU:0", "XLA Modules", "jit_step(1)", 0 * MS, 10 * MS),
        ("/device:TPU:0", "XLA Ops", "%fusion.1 = f32[8] fusion(%p)", 0 * MS,
         6 * MS),
        ("/device:TPU:0", "XLA Ops", "fusion.2", 5 * MS, 5 * MS),
        ("/device:TPU:0", "XLA Ops", "copy.3", 95 * MS, 10 * MS),
        ("/device:TPU:0", "XLA Ops", "fusion.1", -20 * MS, 10 * MS),
        ("/device:TPU:1", "XLA Ops", "fusion.1", 0 * MS, 20 * MS),
        ("/device:TPU:0", "Steps", "0", 0 * MS, 100 * MS),
    ]


def test_busy_idle_and_ops():
    from bench.trace import breakdown, reduce
    red = reduce(events())
    assert red.chips == 2
    assert red.window_s == pytest.approx(0.1)
    # chip 0: [0, 10) and [95, 100) inside the window; chip 1: [0, 20)
    assert red.busy_s == pytest.approx((0.015 + 0.020) / 2)
    # self time: fusion.2 starts inside fusion.1's last millisecond
    assert red.op_seconds == pytest.approx(
        {"fusion.1": 0.005 + 0.020, "fusion.2": 0.005, "copy.3": 0.005})
    assert red.module_seconds == pytest.approx({"jit_step(1)": 0.010})
    # chip 0 idles over [10, 95): most of it under the instant checkpoint
    assert red.idle_gaps[0][0] == "instant_ckpt"
    assert red.idle_gaps[0][1] == pytest.approx(0.085)
    b = breakdown(red)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.025)]
    assert b["idle_gaps"] == [["instant_ckpt", pytest.approx(0.085)]]


def test_nested_ops_count_their_self_time():
    from bench.trace import reduce
    ev = [("/host:CPU", "python", "window", 0, 100 * MS),
          ("/device:TPU:0", "XLA Ops", "%while.3 = (s32[]) while(%t)", 0,
           50 * MS),
          ("/device:TPU:0", "XLA Ops", "fusion.4", 10 * MS, 20 * MS),
          ("/device:TPU:0", "XLA Ops", "fusion.5", 30 * MS, 10 * MS)]
    red = reduce(ev)
    assert red.op_seconds == pytest.approx(
        {"while.3": 0.020, "fusion.4": 0.020, "fusion.5": 0.010})
    assert red.busy_s == pytest.approx(0.050)


def test_a_trace_without_window_or_device_reads_nothing():
    from bench.trace import reduce
    ev = events()
    assert reduce([e for e in ev if e[2] != "window"]) is None
    assert reduce([e for e in ev if not e[0].startswith("/device")]) is None
