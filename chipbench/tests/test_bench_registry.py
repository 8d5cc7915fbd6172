"""Every cell, configuration, traffic mix, driver and per-layer metric in
BENCHMARK.json resolves by name, and the file keeps to its contract.  The
stream driver's fixture cell and its entries resolve the same way."""
import json
import os
import re

import pytest

import registry
from benchcase import STREAM_DIR, stream_entries

BENCH = registry.benchmark()
FIXTURE = stream_entries()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
# (entries, root of their files, cell) for every cell a driver serves.
ALL_CELLS = ([(BENCH, registry.BENCH_DIR, c) for c in CELLS]
             + [(FIXTURE, STREAM_DIR, w["name"])
                for w in FIXTURE["workloads"]])
ALL_PER_LAYER = ([(BENCH, m) for m in BENCH["per_layer"]]
                 + [(FIXTURE, m) for m in FIXTURE["per_layer"]])


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_and_units():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("bench,base,name", ALL_CELLS,
                         ids=[c[2] for c in ALL_CELLS])
def test_cell_resolves(bench, base, name):
    cell = registry.cell(name, bench, base)
    drv = registry.driver(cell.driver)
    for fn in ("setup", "window_run", "end_to_end", "spans", "release",
               "check", "judge", "control_rows"):
        assert callable(getattr(drv, fn)), fn
    assert set(cell.config["limits"])
    if bench is BENCH:
        config = next(w["config"] for w in BENCH["workloads"]
                      if w["name"] == name)
        entry = next(c for c in BENCH["configs"] if c["name"] == config)
        assert os.path.isfile(os.path.join(registry.ROOT, entry["file"]))


def test_fixture_is_not_a_cell():
    for w in FIXTURE["workloads"]:
        with pytest.raises(registry.BadName):
            registry.cell(w["name"], BENCH)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(config):
    assert config["file"] == f"chipbench/configs/{config['name']}.json"
    body = registry.load_json("configs", config["name"])
    assert body["source"] == config["source"]
    assert sorted(body["reduced"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        assert key in body and not key.endswith(("_dim", "_rank"))
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("bench,metric", ALL_PER_LAYER,
                         ids=[m["name"] for _, m in ALL_PER_LAYER])
def test_per_layer_reader(bench, metric):
    assert callable(registry.reader(metric["name"]))
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    moves = e2e[metric["moves"]]
    for cell in metric["workloads"]:
        assert cell in cells
        assert cell in moves.get("workloads", cells)


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] == 0.25


@pytest.mark.parametrize("bench,base,name", ALL_CELLS,
                         ids=[c[2] for c in ALL_CELLS])
def test_each_cell_reports_enough(bench, base, name):
    e2e = registry.metrics_of(bench, name, "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert registry.metrics_of(bench, name, "per_layer")


def test_names_outside_the_benchmark_are_refused():
    for bad in ("../BENCHMARK", "a/b", "", "x" * 65):
        with pytest.raises(registry.BadName):
            registry.load_json("workloads", bad)
