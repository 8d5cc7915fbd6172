"""The reduction from a profiler trace to busy time, per-op time and idle
gaps."""
import os

import pytest

import devtrace

# A profiler trace recorded on a TPU v5e: three calls of a jitted 512 x 512
# matmul under the window's annotation, 2 ms of sleep between them.
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_trace.xplane.pb")

def test_merge_and_gaps():
    busy = devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert devtrace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert devtrace.gaps(busy, 1, 6) == [(3, 5)]
    assert devtrace.gaps([], 2, 4) == [(2, 4)]


def _host():
    # One host thread: the window, the run, and frames inside it.
    return [[(1000, 10000, devtrace.WINDOW),
             (1000, 10000, "$engine.py:597 run"),
             (2000, 3000, "$engine.py:409 _admit_job"),
             (2500, 100, "$api.py:12 device_put"),
             (6000, 2000, "$engine.py:452 _finish"),
             (500, 20000, "$threading.py:1 outer")]]


def test_reduce_events_two_chips():
    dev = [[(1000, 500, "fusion.1"), (1500, 1000, "fusion.2"),
            (8000, 3000, "fusion.1"), (30000, 5, "outside")],
           [(1000, 500, "fusion.1")]]
    out = devtrace.reduce_events(dev, _host())
    assert out["window_s"] == pytest.approx(10000e-9)
    # chip 0 busy [1000,2500] + [8000,11000]; chip 1 busy [1000,1500]
    assert out["busy_s"] == pytest.approx((1500 + 3000 + 500) / 2 * 1e-9)
    ops = out["ops"]
    assert ops["fusion.1"]["count"] == 3 and "outside" not in ops
    assert ops["fusion.1"]["seconds"] == pytest.approx((500 + 3000 + 500)
                                                       / 2 * 1e-9)
    assert out["device_ops"][0][0] == "fusion.1"
    gaps = dict(out["idle_gaps"])
    # chip 0: gap [2500, 8000] midpoint 5250 -> run; chip 1: gap
    # [1500, 11000] midpoint 6250 -> _finish
    assert gaps["$engine.py:597 run"] == pytest.approx(5500 / 2 * 1e-9)
    assert gaps["$engine.py:452 _finish"] == pytest.approx(9500 / 2 * 1e-9)


def test_innermost_host_event_names_a_gap():
    frames = [(s, s + d, n) for s, d, n in _host()[0][1:]]
    assert devtrace.host_activity(frames, [2550, 9000, 100, 2550]) == [
        "$api.py:12 device_put", "$engine.py:597 run",
        devtrace.IDLE_HOST, "$api.py:12 device_put"]


def test_nested_ops_count_their_own_time():
    dev = [[(1000, 6000, "%while.3 = (s32[]) while(..)"),
            (1000, 1000, "%fusion.1 = f32[8] fusion(..)"),
            (3000, 2000, "%fusion.2 = f32[8] fusion(..)"),
            (8000, 1000, "%fusion.1 = f32[8] fusion(..)")]]
    out = devtrace.reduce_events(dev, _host())
    ops = out["ops"]
    assert ops["while.3"]["seconds"] == pytest.approx(3000e-9)
    assert ops["fusion.1"]["seconds"] == pytest.approx(2000e-9)
    assert ops["fusion.1"]["count"] == 2
    assert out["busy_s"] == pytest.approx(7000e-9)


def test_no_window_or_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce_events([[(0, 5, "x")]], [[(0, 9, "other")]])
    with pytest.raises(ValueError):
        devtrace.reduce_events([[(50000, 5, "x")]], _host())


def test_recorded_chip_trace():
    device, host = devtrace.read_xplane(RECORDED)
    assert len(device) == 1 and len(device[0]) > 0
    out = devtrace.reduce_events(device, host)
    assert 0.005 < out["window_s"] < 0.05
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0] == "fusion"
    idle = dict(out["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])
    assert idle["$time sleep"] > 0.005
