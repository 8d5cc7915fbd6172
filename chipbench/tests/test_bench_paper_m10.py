"""The paper's 10-server cell, ``bound.paper_m10``, at a size the CPU
holds: 4 instances over 600 epochs, a search of 48 chains and 60
iterations, the fleet kept at 10 machines.  A sound run reads correct;
a search that returns its start reads not correct there too."""
import dataclasses

import jax

import clock
import registry
import run
from test_bench_faults_bound import _unchanged

CELL = "bound.paper_m10"
SMALL = {"instances": 4, "horizon": 600,
         "sa": {"pop": 48, "iters": 60, "sweeps": 2}}


def _run_small(monkeypatch, seed: int = 3) -> dict:
    monkeypatch.setattr(clock, "use_compile_cache", lambda root: "off")
    bench = registry.benchmark()
    cell = registry.cell(CELL, bench)
    cell = dataclasses.replace(cell, config=dict(cell.config, **SMALL))
    assert cell.config["machines"] == 10
    return run.execute(cell, bench, seed, 0.01, False, None)


def test_sound_run_is_correct(monkeypatch):
    res = _run_small(monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    unimproved = res["checks"]["unimproved_share"]
    assert unimproved["value"] <= unimproved["limit"]
    assert set(res["metrics"]) == {"setup_s", "bound_instances_per_s"}


def test_search_left_unchanged_reads_not_correct(monkeypatch):
    jax.clear_caches()
    _unchanged(monkeypatch)
    res = _run_small(monkeypatch)
    jax.clear_caches()
    assert not res["correct"]
    unimproved = res["checks"]["unimproved_share"]
    assert unimproved["value"] > unimproved["limit"]
