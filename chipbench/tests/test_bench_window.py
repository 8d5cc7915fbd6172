"""The window arithmetic of both drivers, against a fake clock."""
import types

import pytest

import window


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def fake_run(seconds, costs, min_units=1):
    clock = FakeClock()
    seen = []

    def step(i):
        seen.append(i)
        clock.t += costs[len(seen) - 1]
        return 10 + i, f"answer{i}"

    units = window.run(step, 3, seconds, min_units=min_units, clock=clock)
    return units, seen


@pytest.mark.parametrize("seconds,costs,n", [
    (5.0, [2.0, 2.0, 2.0, 2.0], 3),     # the unit that crosses 5 s runs whole
    (4.0, [2.0, 2.0, 2.0], 2),          # ends exactly on the boundary
    (0.5, [3.0, 1.0], 1),               # one unit longer than the window
])
def test_whole_units_until_seconds(seconds, costs, n):
    units, seen = fake_run(seconds, costs)
    assert len(units) == n
    assert [u.index for u in units] == [i % 3 for i in range(n)]
    assert units[-1].end - units[0].start >= seconds
    assert units[-2].end - units[0].start < seconds if n > 1 else True


def test_inputs_cycle_and_min_units():
    units, seen = fake_run(0.1, [1.0] * 5, min_units=4)
    assert [u.index for u in units] == [0, 1, 2, 0]


def test_rate_is_all_work_over_first_start_to_last_end():
    units, _ = fake_run(5.0, [2.0, 1.0, 3.0])
    assert units[0].start == 100.0 and units[-1].end == 106.0
    assert window.rate(units) == pytest.approx((10 + 11 + 12) / 6.0)


def test_traced_wraps_first_unit_only():
    entered = []

    class Ctx:
        def __enter__(self):
            entered.append(1)

        def __exit__(self, *a):
            return False

    clock = FakeClock()

    def step(i):
        clock.t += 1.0
        return 1, None

    units = window.run(step, 2, 2.5, traced=Ctx, clock=clock)
    assert entered == [1]
    assert [u.traced for u in units] == [True, False, False]


def test_percentile_is_over_all_samples():
    xs = list(range(1, 101))
    assert window.percentile(xs, 95) == pytest.approx(95.05)
    assert window.percentile([3.0], 95) == 3.0


def _stream_units():
    mk = lambda s, e, n, adm, tick: window.Unit(  # noqa: E731
        0, s, e, n, ([None] * n, {"admission_wall_s_first": adm[:1],
                                  "admission_wall_s_warm": adm[1:],
                                  "tick_wall_s_first": tick[:1],
                                  "tick_wall_s_warm": tick[1:]}))
    return [mk(0.0, 4.0, 100, [0.001] * 50, [0.01] * 300),
            mk(4.0, 10.0, 200, [0.002] * 49 + [0.5], [0.01] * 500)]


def test_stream_end_to_end():
    import registry
    drv = registry.driver("stream")
    state = types.SimpleNamespace(units=_stream_units())
    e2e = drv.end_to_end(state)
    assert e2e["stream_jobs_per_s"] == pytest.approx(300 / 10.0)
    samples = [0.001] * 50 + [0.002] * 49 + [0.5]
    assert e2e["stream_admit_p95_ms"] == pytest.approx(
        1e3 * window.percentile(samples, 95))
    sp = drv.spans(state)
    assert len(sp["tick_s"]) == 800 and sp["window_s"] == 10.0


def test_stream_span_readers():
    import registry
    drv = registry.driver("stream")
    ctx = {"spans": drv.spans(types.SimpleNamespace(units=_stream_units())),
           "trace": None}
    assert registry.reader("stream.tick_p50_ms")(ctx) == pytest.approx(10.0)
    # 100 admissions: 50 of 1 ms, 49 of 2 ms and one of 500 ms.
    assert registry.reader("stream.admit_p50_ms")(ctx) == pytest.approx(1.5)
    timed = 800 * 0.01 + 50 * 0.001 + 49 * 0.002 + 0.5
    assert registry.reader("stream.host_share")(ctx) == pytest.approx(
        100.0 * (1.0 - timed / 10.0))
    assert registry.reader("stream.idle_share")(ctx) is None


def test_bound_end_to_end():
    import registry
    drv = registry.driver("bound")
    units = [window.Unit(i % 8, 3.0 * i, 3.0 * i + 2.5, 32, None)
             for i in range(4)]
    state = types.SimpleNamespace(units=units)
    assert drv.end_to_end(state)["bound_instances_per_s"] == pytest.approx(
        128 / 11.5)
