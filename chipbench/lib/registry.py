"""Everything a run needs, found by name.

``BENCHMARK.json`` at the checkout's root lists the cells and metrics.  A
cell ``<name>`` is ``workloads/<name>.json``, which names its configuration
(``configs/<config>.json``, whose ``driver`` names ``drivers/<driver>.py``)
and its traffic mix (``traffic/<traffic>.json``).  A per-layer metric
``<name>`` is read by ``metrics/<name>.py``.  Adding a cell or a metric
adds files; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")


class BadName(ValueError):
    pass


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise BadName(f"not a name: {name!r}")
    return name


def load_json(kind: str, name: str, base: str = BENCH_DIR) -> dict:
    with open(os.path.join(base, kind, _checked(name) + ".json")) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    driver: str
    config: dict
    traffic: dict


def cell(name: str, bench: dict, base: str = BENCH_DIR) -> Cell:
    """The cell ``name``, checked against its entry in ``bench`` (the
    parsed ``BENCHMARK.json``); its files are found under ``base``."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BadName(f"no cell {name!r} in BENCHMARK.json")
    spec = load_json("workloads", name, base)
    for key in ("config", "traffic", "chips"):
        if spec[key] != entry[key]:
            raise ValueError(f"cell {name}: {key} is {spec[key]!r} in its "
                             f"file and {entry[key]!r} in BENCHMARK.json")
    config = load_json("configs", spec["config"], base)
    return Cell(name, int(spec["chips"]), config["driver"], config,
                load_json("traffic", spec["traffic"], base))


def _module(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, _checked(name) + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = sys.modules.get(spec.name)
    if mod is None:
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    """``drivers/<name>.py``: ``setup``, ``window``, ``end_to_end``,
    ``check``."""
    return _module("drivers", name)


def reader(metric: str):
    """``metrics/<metric>.py``'s ``read(ctx)``: the metric's value, or None
    where the run has nothing it can read."""
    return _module("metrics", metric).read


def metrics_of(bench: dict, cell_name: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]
