"""Learned gate thetas vs the fixed policy grid, per scenario family.

The online gate's fixed ``(theta, window, stretch)`` grid (PR 1) leaves
savings on the table: the best theta depends on DAG structure, fleet and
stretch budget.  This benchmark trains per-(cell, stretch) thetas with the
differentiable relaxation (:mod:`repro.learn`) — initialized from the best
fixed-grid policy at the same stretch and kept only when the hard-dispatch
evaluation improves on it — and reports learned vs fixed savings per
family at **equal stretch budget**.

Outputs ``BENCH_learn.json``: the per-cell sweep rows with their
``"learned"`` cells, the family x stretch summary, and the acceptance flag
``learned_ge_fixed_everywhere`` (guaranteed by the init-fallback
construction; ``improved_cells`` counts where gradient training moved
strictly past the grid).

    python -m benchmarks.learned_gate             # full grid
    python -m benchmarks.learned_gate --tiny      # CI smoke / golden grid

Everything is deterministic (no PRNG in the relaxation, the loss or the
Adam loop), so equal seeds reproduce the JSON bit-for-bit.
"""
from __future__ import annotations

# Join any jax.distributed fleet before jax-touching imports — see the
# matching prelude in benchmarks/structure_sweep.py.
from repro.shard.distributed import initialize_from_env

initialize_from_env()

import argparse
import os
import time

from benchmarks.common import (bench_timing, use_compile_cache, write_csv,
                               write_json)
from benchmarks.structure_sweep import check_topology, make_spec
from repro.learn import LearnConfig
from repro.scenarios import learned_summary, sweep_structure, trend_summary

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_learn.json")

TINY_LEARN = LearnConfig(steps=60)
FULL_LEARN = LearnConfig(steps=150)


def _csv_row(r: dict) -> dict:
    """Flatten a sweep row's per-stretch learned cells to scalar columns.

    ``learned_S<stretch>_{theta, savings_pct, fixed_best_savings_pct,
    improved}`` — the metrics this benchmark exists to measure, which a
    plain drop-the-dicts filter would lose.
    """
    flat = {k: v for k, v in r.items() if not isinstance(v, (list, dict))}
    for sx_key, cell in r.get("learned", {}).items():
        pfx = f"learned_S{sx_key}_"
        flat[pfx + "theta"] = cell["theta"]
        flat[pfx + "savings_pct"] = cell["savings_pct"]
        flat[pfx + "fixed_best_savings_pct"] = cell["fixed_best_savings_pct"]
        flat[pfx + "improved"] = int(cell["improved"])
    return flat


def run(tiny: bool = False, steps: int | None = None,
        instances_per_cell: int | None = None, out: str | None = None,
        seed: int = 2024, devices: int | None = None,
        processes: int | None = None) -> list[dict]:
    devices, processes = check_topology(devices, processes)
    spec = make_spec(tiny=tiny, instances_per_cell=instances_per_cell,
                     seed=seed)
    cfg = TINY_LEARN if tiny else FULL_LEARN
    if steps is not None:
        cfg = cfg._replace(steps=steps)

    t0 = time.time()
    rows, meta = sweep_structure(spec, offline=False, learn=cfg,
                                 devices=devices, processes=processes)
    seconds = time.time() - t0
    summary, ok = learned_summary(rows)

    record = {
        "bench": "learned_gate",
        "mode": "tiny" if tiny else "full",
        "seconds": round(seconds, 3),
        "timing": bench_timing(seconds),
        **meta,
        "summary_by_family": summary,
        "acceptance": {"learned_ge_fixed_everywhere": ok},
        "trends": trend_summary(rows),
        "cells": rows,
    }
    write_json(out or BENCH_JSON, record)
    write_csv("learned_gate" + ("_tiny" if tiny else ""),
              [_csv_row(r) for r in rows])

    print(f"# learned_gate[{record['mode']}]: {len(rows)} cells x "
          f"{spec.instances_per_cell} instances, {cfg.steps} steps "
          f"in {seconds:.1f}s on {meta['processes']} process(es) x "
          f"{meta['devices']} device(s) — "
          f"learned >= fixed everywhere: {ok}",
          flush=True)
    for fam, by_sx in summary.items():
        for sx, d in by_sx.items():
            print(f"#   {fam} S={sx}: learned "
                  f"{d['learned_savings_pct']:.2f}% vs fixed "
                  f"{d['fixed_best_savings_pct']:.2f}% "
                  f"({d['improved_cells']}/{d['cells']} cells improved)",
                  flush=True)
    if not ok:
        raise AssertionError(
            "learned thetas fell below the fixed grid somewhere — "
            "the init-fallback invariant is broken")
    return rows


def run_harness(instances: int = 16) -> list[dict]:
    """Adapter for ``benchmarks.run`` (instances per cell, clamped)."""
    return run(instances_per_cell=min(8, max(1, instances // 4)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid (the golden-locked cells)")
    ap.add_argument("--steps", type=int, default=None,
                    help="gradient steps (default: mode preset)")
    ap.add_argument("--instances", type=int, default=None,
                    help="instances per cell (default: grid preset)")
    ap.add_argument("--seed", type=int, default=2024)
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the instance axis over N local devices "
                         "(bit-exact; 'seconds'/'devices' record the "
                         "sharded wall clock); with --processes, devices "
                         "per process")
    ap.add_argument("--processes", type=int, default=None,
                    help="span the shards over a P-process jax.distributed "
                         "fleet (bit-exact; run one worker per rank via "
                         "python -m tests.harness --processes P --devices D "
                         "-- <this command>)")
    ap.add_argument("--out", type=str, default=None,
                    help=f"output JSON path (default {BENCH_JSON})")
    args = ap.parse_args()
    use_compile_cache()
    run(tiny=args.tiny, steps=args.steps,
        instances_per_cell=args.instances, out=args.out, seed=args.seed,
        devices=args.devices, processes=args.processes)


if __name__ == "__main__":
    main()
