"""Running a cell on the CPU at a size a test can hold: the chip check is
skipped, the rest of a run is the benchmark's own.

The stream driver has no cell in ``BENCHMARK.json`` yet; its cell is a
fixture under ``data/stream/``, laid out as the benchmark's own files,
with the entries it would have in ``BENCHMARK.json``."""
import dataclasses
import json
import os

import clock
import registry
import run

STREAM_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "stream")

SMALL = {
    "stream.poisson_0.9": {"cfg": {"horizon": 192, "lanes": 4},
                           "traffic": {"jobs": 36}},
    "bound.paper_s1": {"cfg": {"instances": 4, "horizon": 600,
                               "sa": {"pop": 48, "iters": 60, "sweeps": 2}}},
}


def stream_entries() -> dict:
    with open(os.path.join(STREAM_DIR, "entries.json")) as f:
        return json.load(f)


def bench_of(name: str) -> tuple[dict, str]:
    """The benchmark the cell ``name`` belongs to, and its files' root."""
    bench = registry.benchmark()
    if any(w["name"] == name for w in bench["workloads"]):
        return bench, registry.BENCH_DIR
    return stream_entries(), STREAM_DIR


def small_cell(name: str) -> registry.Cell:
    bench, base = bench_of(name)
    cell = registry.cell(name, bench, base)
    over = SMALL[name]
    return dataclasses.replace(
        cell, config=dict(cell.config, **over.get("cfg", {})),
        traffic=dict(cell.traffic, **over.get("traffic", {})))


def run_small(name: str, monkeypatch, seed: int = 3) -> dict:
    """One run of the cell at its small size, on the CPU."""
    monkeypatch.setattr(clock, "use_compile_cache", lambda root: "off")
    return run.execute(small_cell(name), bench_of(name)[0], seed, 0.01,
                       False, None)
