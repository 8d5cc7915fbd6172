"""Streaming dispatch service under load: throughput, queue delay, savings.

The closed-batch sweeps measure what gating saves when every job is known
at t=0.  This benchmark drives the streaming engine (:mod:`repro.stream`)
with continuous arrivals and measures what the batch path cannot see: the
carbon/latency tension of a *finite lane pool*.  Delaying a job into a
cleaner window keeps its lane busy longer, so at high load the queue backs
up — savings are bought with queue delay.

For each (arrival family x load factor) cell the harness calibrates the
arrival rate against the pool's greedy service capacity (``load = arrival
rate / (n_lanes / mean greedy makespan)``), streams one seeded scenario
through :func:`repro.stream.simulate_stream`, and reports

* sustained dispatch throughput (jobs/sec of wall clock, post-warmup);
* the queue-delay distribution (epochs from arrival to lane admission);
* the carbon-savings distribution vs each job's greedy-at-admission
  baseline;
* unfinished/rejected job counts (the overload signal).

Outputs ``BENCH_stream.json`` (repo root by default) plus a per-cell CSV
under ``experiments/bench/``.  Expected shape: savings stay roughly flat
with load (the gate is per-job) while queue delay grows superlinearly as
load approaches 1 — and faster for the bursty family at equal load.

With ``--shared-fleet`` every cell also runs against ONE shared machine set
(``StreamConfig.shared_fleet=True``: lanes contend for machines inside the
epoch, the paper's common-fleet model) and the report gains per-cell
queue-delay/savings deltas vs the partitioned baseline.

    python -m benchmarks.stream_serve                   # full grid
    python -m benchmarks.stream_serve --tiny            # CI smoke grid
    python -m benchmarks.stream_serve --shared-fleet    # both fleet modes
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time

import numpy as np

from benchmarks.common import (bench_timing, use_compile_cache, write_csv,
                               write_json)
from repro.core.instance import Instance, pack
from repro.core.objectives import makespan
from repro.core.solvers.online_jax import online_greedy_jax
from repro.obs import Tracer
from repro.scenarios.fleets import build_fleet
from repro.scenarios.generator import ScenarioConfig, sample_job
from repro.stream import StreamConfig, simulate_stream

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..",
                          "BENCH_stream.json")

# Full grid: 3 arrival families x 4 load factors, day-scale stream.
FULL = dict(horizon=1024, n_lanes=8, family="layered", width=3, depth=3,
            n_machines=3, fleet="tiered", mean_dur=6.0,
            loads=(0.3, 0.6, 0.9, 1.2),
            families=("poisson", "bursty", "diurnal"))

# Tiny grid (CI smoke): 2 families x 3 loads, quarter-day stream.
TINY = dict(horizon=256, n_lanes=4, family="layered", width=3, depth=2,
            n_machines=3, fleet="tiered", mean_dur=5.0,
            loads=(0.4, 0.8, 1.2),
            families=("poisson", "bursty"))


def probe_service_epochs(knobs: dict, seed: int, n_probe: int = 8) -> float:
    """Mean greedy makespan of the cell's job distribution — the per-lane
    service time the load factor is calibrated against."""
    rng = np.random.default_rng(seed)
    scen = ScenarioConfig(family=knobs["family"], n_jobs=1,
                          width=knobs["width"], depth=knobs["depth"],
                          n_machines=knobs["n_machines"],
                          fleet=knobs["fleet"],
                          mean_dur=knobs["mean_dur"]).validate()
    jobs = [dataclasses.replace(sample_job(rng, scen), arrival=0)
            for _ in range(n_probe)]
    powers, speeds = build_fleet(knobs["fleet"], rng, knobs["n_machines"])
    T = max(j.n_tasks for j in jobs)
    ms = []
    for j in jobs:
        inst = pack(Instance(jobs=(j,), powers_kw=powers, speeds=speeds),
                    pad_tasks=T)
        g = online_greedy_jax(inst, 512)
        ms.append(int(makespan(inst, g.start, g.assign)))
    return float(np.mean(ms))


def _dist(xs: list[float]) -> dict:
    if not xs:
        return {"mean": 0.0, "p50": 0.0, "p90": 0.0, "max": 0.0}
    a = np.asarray(xs, np.float64)
    return {"mean": round(float(a.mean()), 3),
            "p50": round(float(np.percentile(a, 50)), 3),
            "p90": round(float(np.percentile(a, 90)), 3),
            "max": round(float(a.max()), 3)}


def _round_dist(d: dict) -> dict:
    return {k: round(v, 3) if isinstance(v, float) else v
            for k, v in d.items()}


def _cell_config(knobs: dict, family: str, rate: float, seed: int,
                 shared_fleet: bool = False) -> StreamConfig:
    return StreamConfig(arrivals=family, rate=rate, horizon=knobs["horizon"],
                        n_lanes=knobs["n_lanes"], family=knobs["family"],
                        width=knobs["width"], depth=knobs["depth"],
                        n_machines=knobs["n_machines"], fleet=knobs["fleet"],
                        mean_dur=knobs["mean_dur"], seed=seed,
                        shared_fleet=shared_fleet)


def run_cell(knobs: dict, family: str, load: float, rate: float,
             seed: int, shared_fleet: bool = False) -> dict:
    cfg = _cell_config(knobs, family, rate, seed, shared_fleet=shared_fleet)
    t0 = time.time()
    res = simulate_stream(cfg)
    seconds = time.time() - t0
    # Counts and distributions come from the engine's own metrics registry
    # (res.summary) — the benchmark no longer re-derives them from job lists.
    s = res.summary
    n_finished = s["jobs_completed"]
    finished = [sj for sj in res.jobs if sj.finished]
    return {
        "arrivals": family,
        "load": load,
        "shared_fleet": shared_fleet,
        "rate_jobs_per_epoch": round(rate, 5),
        "n_jobs": len(res.jobs),
        "n_admitted": s["jobs_admitted"],
        "n_rejected": s["jobs_rejected"],
        "n_finished": n_finished,
        "n_truncated": s["jobs_truncated"],
        "n_unfinished": len(res.jobs) - n_finished,
        "final_lane_occupancy": s["final_lane_occupancy"],
        "seconds": round(seconds, 3),
        "jobs_per_sec": round(n_finished / max(seconds, 1e-9), 2),
        "queue_delay_epochs": _round_dist(s["queue_delay_epochs"]),
        "carbon_savings_pct": _round_dist(s["carbon_savings_pct"]),
        "realized_stretch": _dist(
            [(sj.completed - sj.admitted)
             / max(1, sj.greedy_makespan - sj.admitted)
             for sj in finished]),
    }


def export_trace(path: str, seed: int = 2024) -> str:
    """Stream one tiny traced cell and export its Chrome-trace JSON (the CI
    trace artifact; open at https://ui.perfetto.dev)."""
    knobs = dict(TINY)
    loads, families = knobs.pop("loads"), knobs.pop("families")
    service = probe_service_epochs(knobs, seed)
    rate = loads[0] * knobs["n_lanes"] / service
    tracer = Tracer()
    simulate_stream(_cell_config(knobs, families[0], rate, seed),
                    tracer=tracer)
    lanes = {i: f"lane {i}" for i in range(knobs["n_lanes"])}
    tracer.export(path, lane_names=lanes)
    print(f"# stream_serve: wrote engine trace {path} "
          f"({len(tracer.events)} events)", flush=True)
    return path


def _fleet_deltas(rows: list[dict]) -> list[dict]:
    """Per-(family, load) shared-minus-partitioned deltas: the contention
    cost (queue delay up) and gate-interaction cost (savings down) of one
    common machine set vs disjoint per-lane partitions."""
    part = {(r["arrivals"], r["load"]): r for r in rows
            if not r["shared_fleet"]}
    out = []
    for r in rows:
        if not r["shared_fleet"]:
            continue
        p = part.get((r["arrivals"], r["load"]))
        if p is None:
            continue
        out.append({
            "arrivals": r["arrivals"],
            "load": r["load"],
            "queue_delay_mean_delta": round(
                r["queue_delay_epochs"]["mean"]
                - p["queue_delay_epochs"]["mean"], 3),
            "queue_delay_p90_delta": round(
                r["queue_delay_epochs"]["p90"]
                - p["queue_delay_epochs"]["p90"], 3),
            "savings_mean_delta_pct": round(
                r["carbon_savings_pct"]["mean"]
                - p["carbon_savings_pct"]["mean"], 3),
            "finished_delta": r["n_finished"] - p["n_finished"],
        })
    return out


def run(tiny: bool = False, out: str | None = None,
        seed: int = 2024, shared_fleet: bool = False) -> list[dict]:
    """``shared_fleet=True`` runs each cell in BOTH fleet modes (partitioned
    baseline + one shared machine set) and reports per-cell deltas."""
    knobs = dict(TINY if tiny else FULL)
    loads = knobs.pop("loads")
    families = knobs.pop("families")
    service = probe_service_epochs(knobs, seed)
    capacity = knobs["n_lanes"] / service      # jobs/epoch the pool clears
    fleet_modes = (False, True) if shared_fleet else (False,)
    # Warmup cell outside the clock so per-cell seconds are post-compile.
    for sf in fleet_modes:
        run_cell(knobs, families[0], loads[0], loads[0] * capacity, seed,
                 shared_fleet=sf)

    t0 = time.time()
    rows = [run_cell(knobs, fam, load, load * capacity, seed,
                     shared_fleet=sf)
            for sf in fleet_modes for fam in families for load in loads]
    seconds = time.time() - t0

    record = {
        "bench": "stream_serve",
        "mode": "tiny" if tiny else "full",
        "shared_fleet_axis": shared_fleet,
        "seconds": round(seconds, 3),
        "timing": bench_timing(seconds),
        "seed": seed,
        "service_epochs": round(service, 3),
        "capacity_jobs_per_epoch": round(capacity, 5),
        **{k: v for k, v in knobs.items()},
        "cells": rows,
    }
    if shared_fleet:
        record["fleet_deltas"] = _fleet_deltas(rows)
    write_json(out or BENCH_JSON, record)
    write_csv("stream_serve" + ("_tiny" if tiny else ""),
              [{k: v for k, v in r.items() if not isinstance(v, dict)}
               for r in rows])

    print(f"# stream_serve[{record['mode']}]: {len(rows)} cells in "
          f"{seconds:.1f}s (service={service:.1f} epochs, "
          f"capacity={capacity:.4f} jobs/epoch)", flush=True)
    for r in rows:
        tag = " shared" if r["shared_fleet"] else ""
        print(f"#   {r['arrivals']:>7} load={r['load']:.1f}{tag}: "
              f"{r['n_finished']}/{r['n_jobs']} finished, "
              f"delay p90={r['queue_delay_epochs']['p90']}, "
              f"savings mean={r['carbon_savings_pct']['mean']}%, "
              f"{r['jobs_per_sec']} jobs/s", flush=True)
    for d in record.get("fleet_deltas", ()):
        print(f"#   delta {d['arrivals']:>7} load={d['load']:.1f}: "
              f"delay mean {d['queue_delay_mean_delta']:+.2f} epochs, "
              f"savings {d['savings_mean_delta_pct']:+.2f}pp", flush=True)
    return rows


def run_harness(instances: int = 16) -> list[dict]:
    """Adapter for ``benchmarks.run`` — small ``--instances`` requests map
    to the tiny grid (the stream length is the cost axis here)."""
    return run(tiny=instances <= 16)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke grid")
    ap.add_argument("--shared-fleet", action="store_true",
                    help="add the shared-fleet axis: run every cell in both "
                         "fleet modes and report contention deltas")
    ap.add_argument("--seed", type=int, default=2024)
    ap.add_argument("--out", type=str, default=None,
                    help=f"output JSON path (default {BENCH_JSON})")
    ap.add_argument("--trace-out", type=str, default=None, metavar="PATH",
                    help="skip the grid; stream one tiny traced cell and "
                         "export its Chrome-trace JSON to PATH")
    args = ap.parse_args()
    use_compile_cache()
    if args.trace_out:
        export_trace(args.trace_out, seed=args.seed)
        return
    run(tiny=args.tiny, out=args.out, seed=args.seed,
        shared_fleet=args.shared_fleet)


if __name__ == "__main__":
    main()
