"""Telemetry subsystem contracts (``src/repro/obs`` + the bench harness).

Four groups:

* **metrics** — counter/gauge/histogram semantics, snapshot shape, the
  get-or-create registry (type conflicts are errors), reset;
* **tracer** — event capture, the Chrome-trace export contract (the JSON
  Perfetto opens: sim epochs on one pid at 1 ms/epoch, wall spans on
  another, metadata + counter tracks), ``REPRO_TRACE`` activation, the
  null tracer's zero-surface, the host events a profiler's trace gets
  (engine phases, ``xla:`` spans), and the bound's stage scopes;
* **bit-exactness** — the subsystem's hard contract: telemetry ON must
  not change a single computed value.  Property-tested over DAG families
  x fleets x both machine rules by running the same stream twice;
* **harness** — fake-clock BenchTimer (cold/warm split is arithmetic,
  locked without real timing), perf-gate verdict logic on fake probes
  (regression / pass / fingerprint-skip / no-baseline skip), provenance
  checks.
"""
import dataclasses
import glob
import json
import os

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from benchmarks.common import BenchTimer
from benchmarks.perf_gate import (check_provenance, extract_probe,
                                  gate_verdict)
from repro.obs import (MetricsRegistry, NULL_TRACER, Tracer, get_tracer,
                       set_tracer, trace_enabled, traced_xla_call)
from repro.scenarios.fleets import build_fleet
from repro.scenarios.generator import ScenarioConfig, sample_job
from repro.core.carbon import sample_window, synthesize
from repro.stream import StreamEngine
from tests.strategies import family_names, fleet_names, seeds

N_MACHINES = 3
PAD_TASKS = 8
HORIZON = 400


def _stream_case(seed, family, fleet, n=3, arrival_step=0):
    rng = np.random.default_rng(seed)
    scen = ScenarioConfig(family=family, n_jobs=1, width=2, depth=2,
                          n_machines=N_MACHINES, fleet=fleet).validate()
    jobs = [dataclasses.replace(sample_job(rng, scen), arrival=i * arrival_step)
            for i in range(n)]
    powers, speeds = build_fleet(fleet, rng, N_MACHINES)
    trace = sample_window(synthesize("AU-SA", days=10, seed=7), rng, HORIZON)
    return jobs, powers, speeds, trace


# ---------------------------------------------------------------------------
# Metrics registry.
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("jobs")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert reg.counter("jobs") is c            # get-or-create returns same
    g = reg.gauge("occupancy")
    g.set(2)
    g.set(7)
    assert g.value == 7


def test_histogram_percentiles_and_snapshot():
    reg = MetricsRegistry()
    h = reg.histogram("delay")
    for v in [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 10
    assert snap["mean"] == pytest.approx(5.5)
    assert snap["p50"] == pytest.approx(np.percentile(range(1, 11), 50))
    assert snap["p90"] == pytest.approx(np.percentile(range(1, 11), 90))
    assert snap["max"] == 10


def test_registry_snapshot_flat_sorted_json_safe():
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.gauge("a").set(1.5)
    reg.histogram("c").observe(2.0)
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    json.dumps(snap)                            # plain python scalars only
    reg.reset()
    assert reg.counter("b").value == 0
    assert reg.histogram("c").snapshot()["count"] == 0


def test_registry_type_conflict_is_error():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


# ---------------------------------------------------------------------------
# Tracer + Chrome-trace export.
# ---------------------------------------------------------------------------

def test_tracer_chrome_export_contract(tmp_path):
    tr = Tracer(clock=iter(np.arange(0.0, 10.0, 0.5)).__next__)
    tr.instant("admit", 3, rid=0, lane=1)
    tr.span("job:0", 3, 17, lane=1, rid=0)
    tr.counter("queue_len", 5, 2.0)
    out = tr.timed("probe", lambda: 41 + 1)
    assert out == 42
    doc = tr.to_chrome_trace(lane_names={1: "lane 1"})
    path = tmp_path / "trace.json"
    tr.export(str(path), lane_names={1: "lane 1"})
    on_disk = json.loads(path.read_text())
    assert on_disk == doc

    ev = doc["traceEvents"]
    meta = [e for e in ev if e["ph"] == "M"]
    assert any(e["args"].get("name") == "lane 1" for e in meta
               if e["name"] == "thread_name")
    span = next(e for e in ev if e["ph"] == "X" and e["name"] == "job:0")
    assert span["ts"] == 3 * 1000 and span["dur"] == (17 - 3) * 1000
    inst = next(e for e in ev if e["ph"] == "i" and e["name"] == "admit")
    assert inst["ts"] == 3 * 1000 and inst["args"]["rid"] == 0
    ctr = next(e for e in ev if e["ph"] == "C")
    assert ctr["args"] == {"value": 2.0}
    wall = next(e for e in ev if e["name"] == "xla:probe")
    assert wall["ph"] == "X" and wall["dur"] == pytest.approx(0.5e6)
    assert wall["args"]["first_call"] is True


def test_null_tracer_records_nothing():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.instant("x", 0)
    NULL_TRACER.span("x", 0, 1)
    NULL_TRACER.counter("x", 0, 1.0)
    assert NULL_TRACER.timed("x", lambda: 7) == 7
    assert NULL_TRACER.events == []


def test_get_tracer_honors_repro_trace_env(monkeypatch):
    set_tracer(None)
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert get_tracer() is NULL_TRACER
    assert not trace_enabled()
    monkeypatch.setenv("REPRO_TRACE", "1")
    tr = get_tracer()
    assert tr.enabled and trace_enabled()
    assert get_tracer() is tr                   # env activation is sticky
    set_tracer(None)
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert get_tracer() is NULL_TRACER
    set_tracer(None)


def test_traced_xla_call_passthrough_and_capture():
    set_tracer(None)
    assert traced_xla_call("f", lambda a, b: a + b, 2, b=3) == 5
    tr = Tracer()
    set_tracer(tr)
    try:
        assert traced_xla_call("f", lambda a, b: a + b, 2, b=3) == 5
        assert [e["name"] for e in tr.events] == ["xla:f"]
    finally:
        set_tracer(None)


def _host_event_names(logdir) -> set:
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                       recursive=True)
    return {ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_profiler_trace_names_engine_phases_and_xla_spans(tmp_path):
    """A profile of a stream and of a traced jitted call holds the
    engine's admission, tick and eviction and the call's ``xla:`` span as
    host events, on the clock of the device's ops."""
    import jax
    import jax.numpy as jnp
    jobs, powers, speeds, trace = _stream_case(11, "layered", "tiered", n=3,
                                               arrival_step=3)
    eng = StreamEngine(trace, powers, speeds, n_lanes=2, pad_tasks=PAD_TASKS)
    step = jax.jit(lambda x: x + 1)
    tr = Tracer()
    set_tracer(tr)
    try:
        with jax.profiler.trace(str(tmp_path)):
            eng.run(jobs)
            traced_xla_call("probe", step, jnp.ones(3))
    finally:
        set_tracer(None)
    assert {"stream.admission", "stream.tick", "stream.eviction",
            "xla:probe"} <= _host_event_names(tmp_path)


def test_scopes_name_the_bounds_stages_and_nothing_else():
    import jax
    import jax.numpy as jnp
    from repro.obs.scopes import PHASES, STAGES, scope

    def f(x):
        with scope("phase2"), scope("timing_sweep"):
            return jnp.sin(x)

    text = jax.jit(f).lower(jnp.ones(3)).as_text(debug_info=True)
    assert "phase2/timing_sweep/sin" in text
    for name in STAGES + PHASES:
        with scope(name):
            pass
    with pytest.raises(ValueError, match="not a stage"):
        scope("decode")


# ---------------------------------------------------------------------------
# The hard contract: telemetry ON is bit-exact to telemetry OFF.
# ---------------------------------------------------------------------------

def _assert_stream_bit_exact(seed, family, fleet, machine_rule):
    jobs, powers, speeds, trace = _stream_case(seed, family, fleet, n=3,
                                               arrival_step=5)

    def run(tracer):
        eng = StreamEngine(trace, powers, speeds, n_lanes=2,
                           pad_tasks=PAD_TASKS, machine_rule=machine_rule,
                           tracer=tracer)
        return eng.run(list(jobs)), eng

    off, _ = run(NULL_TRACER)
    on, eng_on = run(Tracer())
    assert len(eng_on.tracer.events) > 0
    assert len(off) == len(on)
    for a, b in zip(off, on):
        assert (a.admitted, a.completed, a.finished, a.budget) == \
               (b.admitted, b.completed, b.finished, b.budget)
        assert a.carbon == b.carbon and a.energy == b.energy
        if a.start is not None:
            np.testing.assert_array_equal(a.start, b.start)
            np.testing.assert_array_equal(a.assign, b.assign)


@settings(max_examples=6, deadline=None, derandomize=True)
@given(seed=seeds(), family=family_names(), fleet=fleet_names(),
       machine_rule=st.sampled_from(["earliest_finish", "min_energy"]))
def test_stream_bit_exact_with_tracing(seed, family, fleet, machine_rule):
    _assert_stream_bit_exact(seed, family, fleet, machine_rule)


# Fixed-seed grid so the contract holds in CI even under the hypothesis
# stub (where @given property tests skip): one cell per DAG family x a
# fleet, crossed with both machine rules.
@pytest.mark.parametrize("machine_rule", ["earliest_finish", "min_energy"])
@pytest.mark.parametrize("family,fleet", [
    ("chain", "homog"), ("fanout", "tiered"), ("diamond", "mixed"),
    ("layered", "tiered"), ("tpch", "homog")])
def test_stream_bit_exact_with_tracing_grid(family, fleet, machine_rule):
    _assert_stream_bit_exact(17, family, fleet, machine_rule)


def test_stream_summary_matches_job_list():
    jobs, powers, speeds, trace = _stream_case(11, "layered", "tiered", n=5,
                                               arrival_step=3)
    eng = StreamEngine(trace, powers, speeds, n_lanes=2, pad_tasks=PAD_TASKS)
    sjobs = eng.run(jobs)
    s = eng.summary()
    assert s["jobs_admitted"] == sum(1 for sj in sjobs if sj.admitted >= 0)
    assert s["jobs_completed"] == sum(1 for sj in sjobs if sj.finished)
    assert s["jobs_rejected"] == 0
    assert s["queue_delay_epochs"]["count"] == s["jobs_admitted"]
    assert s["carbon_savings_pct"]["count"] == s["jobs_completed"]
    assert s["ticks"] > 0
    json.dumps(s)
    # Re-entrancy: a second run resets the registry, not accumulates.
    eng.run(jobs)
    assert eng.summary()["jobs_admitted"] == s["jobs_admitted"]


# ---------------------------------------------------------------------------
# Bench harness: fake-clock timer, perf-gate verdicts, provenance checks.
# ---------------------------------------------------------------------------

class FakeClock:
    """Deterministic clock: each call returns the next scripted tick."""

    def __init__(self, step=1.0):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def test_bench_timer_fake_clock_cold_warm_split():
    timer = BenchTimer(clock=FakeClock(step=1.0))
    out, timing = timer.cold_warm(lambda x: x * 2, 21, warm_reps=3)
    assert out == 42
    # Each timed() consumes exactly two ticks of the fake clock, so every
    # measured duration is exactly 1.0 — the split is pure bookkeeping.
    assert timing["compile_s"] == pytest.approx(1.0)
    assert timing["warm_s_median"] == pytest.approx(1.0)
    assert timing["warm_s_all"] == [1.0, 1.0, 1.0]


def test_bench_timer_timed_returns_result_and_duration():
    timer = BenchTimer(clock=FakeClock(step=0.25))
    out, secs = timer.timed(sum, [1, 2, 3])
    assert out == 6 and secs == pytest.approx(0.25)


def _probe(fp, dispatch=0.010, learn=0.020):
    return {"fingerprint": fp,
            "cells": {"dispatch_sweep": {"warm_s_median": dispatch},
                      "learn_step": {"warm_s_median": learn}}}


FP = {"backend": "cpu", "device_kind": "cpu", "device_count": 1}
FP_OTHER = {"backend": "tpu", "device_kind": "v5e", "device_count": 4}


def test_gate_passes_within_tolerance():
    v = gate_verdict(_probe(FP, 0.012, 0.021),
                     [("BENCH_a.json", _probe(FP))], tolerance=0.30)
    assert v["ok"] and len(v["compared"]) == 2
    assert all(r["ok"] for r in v["compared"])


def test_gate_detects_regression():
    v = gate_verdict(_probe(FP, dispatch=0.014),
                     [("BENCH_a.json", _probe(FP, dispatch=0.010))],
                     tolerance=0.30)
    row = next(r for r in v["compared"] if r["cell"] == "dispatch_sweep")
    assert not row["ok"] and not v["ok"]
    assert row["ratio"] == pytest.approx(1.4)


def test_gate_uses_best_stored_baseline():
    v = gate_verdict(_probe(FP, dispatch=0.012),
                     [("BENCH_slow.json", _probe(FP, dispatch=0.020)),
                      ("BENCH_fast.json", _probe(FP, dispatch=0.010))])
    row = next(r for r in v["compared"] if r["cell"] == "dispatch_sweep")
    assert row["baseline_warm_s"] == 0.010
    assert row["baseline_from"] == "BENCH_fast.json"


def test_gate_skips_foreign_fingerprints():
    v = gate_verdict(_probe(FP), [("BENCH_tpu.json", _probe(FP_OTHER))])
    assert v["ok"] and v["compared"] == []      # skip path: pass, no rows
    assert v["skipped"][0]["path"] == "BENCH_tpu.json"
    # --cross-machine forces the comparison through.
    v2 = gate_verdict(_probe(FP), [("BENCH_tpu.json", _probe(FP_OTHER))],
                      cross_machine=True)
    assert len(v2["compared"]) == 2 and v2["skipped"] == []


def test_gate_skip_when_no_baselines():
    v = gate_verdict(_probe(FP), [])
    assert v["ok"] and v["compared"] == [] and v["skipped"] == []


def test_extract_probe_shapes():
    assert extract_probe({}) is None
    assert extract_probe({"timing": {"wall_s": 1.0}}) is None
    p = _probe(FP)
    assert extract_probe({"timing": {"probe": p}}) == p


def test_check_provenance(tmp_path):
    good = {"bench": "x", "provenance": {
        "git_sha": "abc", "jax": "0.4", "jaxlib": "0.4", "backend": "cpu",
        "device_kind": "cpu", "device_count": 1}}
    bad = {"bench": "y", "provenance": {"git_sha": "abc"}}
    none = {"bench": "z"}
    for name, rec in [("good.json", good), ("bad.json", bad),
                      ("none.json", none)]:
        (tmp_path / name).write_text(json.dumps(rec))
    assert check_provenance([str(tmp_path / "good.json")]) == []
    missing = check_provenance([str(tmp_path / "bad.json")])
    assert any("jaxlib" in m for m in missing)
    assert any("missing provenance block" in m
               for m in check_provenance([str(tmp_path / "none.json")]))
    assert check_provenance([str(tmp_path / "nope-*.json")])  # no match fails


def test_compile_cache_dir_env_wins_else_fixed_repo_path(monkeypatch,
                                                         tmp_path):
    """The environment's cache directory is left to JAX; otherwise the
    cache goes to one fixed, gitignored path in the checkout."""
    import os

    import jax

    from benchmarks import common
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert common.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = common.use_compile_cache()
        assert path == os.path.join(common.REPO_ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(common.REPO_ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
