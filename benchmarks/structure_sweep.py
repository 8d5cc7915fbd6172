"""Savings vs. structure and server count, at sweep scale.

The paper's sensitivity analysis reports that job structure and server
count set the achievable carbon reduction.  This benchmark reproduces that
trend with the scenario subsystem (:mod:`repro.scenarios`): a grid of
family x (width, depth) x server-count x fleet cells, every cell's
instances padded and stacked into ONE batch, dispatched by the carbon-gated
online scheduler across a gate-policy grid and bounded by the offline SA
bi-level solve — two XLA programs for the whole grid, a scale the
sequential numpy event loop could never reach.

Outputs ``BENCH_structure.json`` (repo root by default): one row per cell
plus the trend summary (savings by family / server count / fleet).  The
expected qualitative shape, matching the paper: savings grow with server
count and with slack-rich (parallelism-friendly, low-utilization)
structures, and the online gate captures a large fraction of the offline
bound.

    python -m benchmarks.structure_sweep             # full grid
    python -m benchmarks.structure_sweep --tiny      # CI smoke / golden grid
    python -m benchmarks.structure_sweep --no-offline  # dispatch only

``--tiny`` is the exact grid the golden regression test
(``tests/test_structure_golden.py``) locks; CI runs it every push and
uploads the JSON as an artifact.
"""
from __future__ import annotations

# jax.distributed must initialize before ANY jax computation, and some
# transitive imports below build module-level jnp constants — so join the
# fleet (a no-op in a plain single-process run, see docs/sharding.md)
# before importing anything that touches jax.
from repro.shard.distributed import initialize_from_env

initialize_from_env()

import argparse
import os
import time

from benchmarks.common import (bench_timing, use_compile_cache, write_csv,
                               write_json)
from repro.core.solvers.annealing import SAConfig
from repro.scenarios import (SweepSpec, structure_cells, sweep_structure,
                             trend_summary)

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_structure.json")

FAMILIES = ("chain", "fanout", "diamond", "layered", "tpch")

# Sizes are per-family (width, depth) pairs chosen so *tasks per job* match
# across families at each size step — the family axis then compares
# structure at equal load (the paper's Fig. 3 comparison), not structure
# confounded with job size.  Task counts: chain = depth, fanout =
# 2 + width*depth, diamond = depth*(width+2), layered ~ depth*(width+1)/2,
# tpch = 2*width - 1 + depth.

# Full grid: 5 families x 2 sizes (6 and 10 tasks/job) x 3 server counts
# x 2 fleets = 60 cells.
FULL = dict(sizes={"chain": ((1, 6), (1, 10)),
                   "fanout": ((2, 2), (4, 2)),
                   "diamond": ((1, 2), (3, 2)),
                   "layered": ((3, 3), (4, 4)),
                   "tpch": ((3, 1), (4, 3))},
            machine_counts=(2, 5, 8),
            fleets=("homog", "tiered"), n_jobs=6,
            instances_per_cell=4, horizon=2048,
            sa=SAConfig(pop=24, iters=40, sweeps=1))

# Tiny grid (CI smoke + golden lock): 5 x 1 size (4 tasks/job) x 2 x 2 =
# 20 cells, 2 instances each.
TINY = dict(sizes={"chain": ((1, 4),),
                   "fanout": ((2, 1),),
                   "diamond": ((2, 1),),
                   "layered": ((3, 2),),
                   "tpch": ((2, 1),)},
            machine_counts=(2, 4),
            fleets=("homog", "tiered"), n_jobs=4,
            instances_per_cell=2, horizon=768,
            sa=SAConfig(pop=16, iters=24, sweeps=1))


def make_spec(tiny: bool = False, instances_per_cell: int | None = None,
              seed: int = 2024) -> SweepSpec:
    knobs = dict(TINY if tiny else FULL)
    sa = knobs.pop("sa")
    n_jobs = knobs.pop("n_jobs")
    ipc = instances_per_cell or knobs.pop("instances_per_cell")
    knobs.pop("instances_per_cell", None)
    horizon = knobs.pop("horizon")
    cells = structure_cells(families=FAMILIES, n_jobs=n_jobs, **knobs)
    return SweepSpec(cells=cells, instances_per_cell=ipc, seed=seed,
                     horizon=horizon, sa=sa)


def check_devices(devices: int | None) -> int | None:
    """Validate a ``--devices`` request against the visible platform."""
    if devices is None:
        return None
    import jax
    if devices > len(jax.devices()):
        raise SystemExit(
            f"--devices {devices}: only {len(jax.devices())} local "
            "device(s) visible — on CPU, force fake devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices}")
    return int(devices)


def check_topology(devices: int | None,
                   processes: int | None) -> tuple[int | None, int | None]:
    """Join the ``jax.distributed`` fleet (if the ``REPRO_*`` env names
    one) and validate ``--devices``/``--processes`` against it.

    Must run before anything touches jax devices — process topology locks
    at first backend init.  Single-process (``processes=None``) reduces to
    :func:`check_devices`; with ``--processes`` the command must be
    running once per rank (``python -m tests.harness --processes P
    --devices D -- <this command>`` spawns that), and ``devices`` counts
    fake devices *per process*.
    """
    from repro.shard.distributed import initialize_from_env
    initialize_from_env()
    if processes is None:
        return check_devices(devices), None
    import jax
    if jax.process_count() != processes:
        raise SystemExit(
            f"--processes {processes}: this run has {jax.process_count()} "
            "jax process(es) — launch one worker per rank, e.g. "
            f"python -m tests.harness --processes {processes} "
            f"--devices {devices or 1} -- <this command>")
    if devices is not None and devices > len(jax.local_devices()):
        raise SystemExit(
            f"--devices {devices}: only {len(jax.local_devices())} local "
            "device(s) per process — the harness forces "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices} "
            "in every worker")
    return devices, int(processes)


def run(tiny: bool = False, offline: bool = True,
        instances_per_cell: int | None = None, out: str | None = None,
        seed: int = 2024, devices: int | None = None,
        processes: int | None = None) -> list[dict]:
    devices, processes = check_topology(devices, processes)
    spec = make_spec(tiny=tiny, instances_per_cell=instances_per_cell,
                     seed=seed)
    t0 = time.time()
    rows, meta = sweep_structure(spec, offline=offline, devices=devices,
                                 processes=processes)
    seconds = time.time() - t0

    trends = trend_summary(rows)
    record = {
        "bench": "structure_sweep",
        "mode": "tiny" if tiny else "full",
        "seconds": round(seconds, 3),
        "timing": bench_timing(seconds),
        **meta,
        "trends": trends,
        "cells": rows,
    }
    write_json(out or BENCH_JSON, record)
    write_csv("structure_sweep" + ("_tiny" if tiny else ""),
              [{k: v for k, v in r.items()
                if not isinstance(v, (list, dict))} for r in rows])

    print(f"# structure_sweep[{record['mode']}]: {len(rows)} cells x "
          f"{spec.instances_per_cell} instances in {seconds:.1f}s "
          f"on {meta['processes']} process(es) x {meta['devices']} "
          f"device(s) (pad T={meta['pad_tasks']}, M={meta['pad_machines']})",
          flush=True)
    for key, series in trends.items():
        print(f"#   {key}: {series}", flush=True)
    return rows


def run_harness(instances: int = 16) -> list[dict]:
    """Adapter for ``benchmarks.run`` (its ``--instances`` is the per-setup
    batch size; here it maps to instances per grid cell, clamped)."""
    return run(instances_per_cell=min(8, max(1, instances // 4)))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid (the golden-locked cells)")
    ap.add_argument("--no-offline", action="store_true",
                    help="skip the offline SA bound (dispatch only)")
    ap.add_argument("--instances", type=int, default=None,
                    help="instances per cell (default: grid preset)")
    ap.add_argument("--seed", type=int, default=2024)
    ap.add_argument("--devices", type=int, default=None,
                    help="shard the instance axis over N local devices "
                         "(bit-exact with the single-device sweep; the "
                         "'seconds'/'devices' columns record the sharded "
                         "wall clock); with --processes, devices per "
                         "process")
    ap.add_argument("--processes", type=int, default=None,
                    help="span the shards over a P-process jax.distributed "
                         "fleet (bit-exact; run one worker per rank via "
                         "python -m tests.harness --processes P --devices D "
                         "-- <this command>)")
    ap.add_argument("--out", type=str, default=None,
                    help=f"output JSON path (default {BENCH_JSON})")
    args = ap.parse_args()
    use_compile_cache()
    run(tiny=args.tiny, offline=not args.no_offline,
        instances_per_cell=args.instances, out=args.out, seed=args.seed,
        devices=args.devices, processes=args.processes)


if __name__ == "__main__":
    main()
