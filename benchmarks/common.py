"""Shared harness for the paper-reproduction benchmarks.

Each benchmark solves a batch of FJSP instances with the bi-level protocol
(Section 3.1): phase 1 optimal makespan (carbon-agnostic baseline), phase 2
carbon/energy under ``makespan <= S x OPT``.  Instances follow the paper's
Section 3.1 setup: n jobs x k tasks, M servers (homogeneous 1 kW or the
5-class heterogeneous menu), exp(7)-epoch durations, arrivals uniform in
24 h, Fig. 3 DAG shapes, AU-SA 2024-style carbon trace, 15-min epochs.

The whole batch is one vmapped XLA program (`solve_bilevel_batch`).  The
paper averages 1000 instances; ``--instances`` trades runtime for CI width
on this 1-core container (defaults keep the full ``benchmarks.run`` under
~15 min; results match the paper's numbers within a few points either way
— see EXPERIMENTS.md).
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import platform
import subprocess
import time
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import generate_instance, pack, stack_packed, synthesize
from repro.core.carbon import CarbonTrace
from repro.core.instance import Instance
from repro.core.solvers import solve_bilevel_batch
from repro.core.solvers.annealing import SAConfig

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                       "bench")

# The persistent compile cache's default home: a fixed path in the checkout
# (the path is part of the cache key, so it must not move between runs).
COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Every entry point calls this before its
    first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR

# Solver budget per phase (paper: CP-SAT 1-5 min timeouts; our TPU-style
# population search uses fixed iteration budgets).
SA_FAST = SAConfig(pop=96, iters=150, sweeps=2)

DEF_HORIZON = 1500     # epochs of carbon trace per instance window


@dataclasses.dataclass(frozen=True)
class BenchSetup:
    n_jobs: int = 10
    k_tasks: int = 4
    n_machines: int = 5
    heterogeneous: bool = False
    region: str = "AU-SA"
    stretch: float = 1.0
    objective: str = "carbon"
    instances: int = 24
    seed: int = 2024


# ---------------------------------------------------------------------------
# Benchmark provenance: every write_json-emitted BENCH_*.json is stamped so
# a number can always be traced back to the code, toolchain and hardware
# that produced it (the ROADMAP's "tracked, regression-locked quantity").
# ---------------------------------------------------------------------------

def _git(*args: str) -> str:
    try:
        return subprocess.run(
            ["git", "-C", REPO_ROOT, *args], capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:
        return ""


def machine_fingerprint() -> dict:
    """The fields that must match for wall-clock comparisons to mean
    anything — the perf gate refuses to compare across fingerprints."""
    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "device_kind": str(dev.device_kind),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "machine": platform.machine(),
    }


def provenance() -> dict:
    """Git SHA, jax/jaxlib versions, device kind/count, process count,
    timestamp.  ``processes`` > 1 marks a record produced by a
    ``jax.distributed`` fleet (``device_count`` is then the global count
    across every process) — ``perf_gate --check-provenance`` validates the
    column's consistency."""
    import jaxlib
    return {
        "git_sha": _git("rev-parse", "HEAD") or "unknown",
        "git_dirty": bool(_git("status", "--porcelain")),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "python": platform.python_version(),
        **machine_fingerprint(),
        "processes": jax.process_count(),
        "timestamp_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }


# ---------------------------------------------------------------------------
# Timing hygiene: every timed region syncs explicitly (block_until_ready),
# and cold (compile) is separated from warm medians.  The clock is
# injectable so the harness itself is unit-testable with a fake clock.
# ---------------------------------------------------------------------------

class BenchTimer:
    """Synced timing with an injectable clock (tests fake it)."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock

    def timed(self, fn: Callable, *args, **kwargs):
        """``(result, seconds)`` with an explicit device sync inside the
        timed region — async dispatch can never leak out of the clock."""
        t0 = self.clock()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        return out, self.clock() - t0

    def cold_warm(self, fn: Callable, *args, warm_reps: int = 3, **kwargs):
        """One cold call (compile + execute) then ``warm_reps`` warm calls.

        Returns ``(result, timing)`` where timing separates ``compile_s``
        (the cold call; an upper bound that includes one execution) from
        the warm median — the quantity the perf gate locks.
        """
        out, cold = self.timed(fn, *args, **kwargs)
        warms = [self.timed(fn, *args, **kwargs)[1]
                 for _ in range(warm_reps)]
        return out, {
            "compile_s": round(cold, 6),
            "warm_s_median": round(float(np.median(warms)), 6),
            "warm_s_all": [round(w, 6) for w in warms],
        }


# ---------------------------------------------------------------------------
# The pinned perf-probe cells.  Tiny, seed-pinned, shape-static programs
# covering the two hot paths (the dispatch sweep and the gate-learner
# step), compiled AOT so the probe measures compile and warm wall-clock
# separately.  Every benchmark stamps probe results into its
# BENCH_*.json; benchmarks/perf_gate.py compares fresh probe warm medians
# against those stored baselines.
# ---------------------------------------------------------------------------

PROBE_SEED = 7
PROBE_HORIZON = 256
PROBE_WARM_REPS = 7


def _probe_batch(n_instances: int = 4):
    """Pinned instance batch + carbon windows shared by the probe cells."""
    rng = np.random.default_rng(PROBE_SEED)
    year = synthesize("AU-SA", days=30, seed=PROBE_SEED)
    packs, intens, cums = [], [], []
    for _ in range(n_instances):
        inst = generate_instance(rng, n_jobs=4, k_tasks=3, n_machines=3)
        packs.append(pack(inst, pad_tasks=12))
        w = year.window(int(rng.integers(0, year.n_epochs - PROBE_HORIZON)),
                        PROBE_HORIZON)
        intens.append(w.intensity)
        cums.append(w.cumulative())
    return (stack_packed(packs), jnp.asarray(np.stack(intens)),
            jnp.asarray(np.stack(cums)))


def _lower_dispatch_probe():
    from repro.core.solvers.online_jax import _sweep
    batch, inten, _ = _probe_batch()
    args = (batch, inten, jnp.asarray([0.3, 0.5], jnp.float32),
            jnp.asarray([48], jnp.int32),
            jnp.asarray([1.25, 1.5], jnp.float32))
    lowered = _sweep.lower(*args, n_epochs=PROBE_HORIZON, max_window=48,
                           machine_rule="earliest_finish")
    return lowered, args


def _lower_learn_probe():
    from repro.learn import LearnConfig
    from repro.learn.train import _train, greedy_reference
    batch, inten, cum = _probe_batch()
    B = int(inten.shape[0])
    ms0, base_c = greedy_reference(batch, cum, PROBE_HORIZON,
                                   "earliest_finish")
    budget = (jnp.float32(1.5) * ms0.astype(jnp.float32)).astype(jnp.int32)
    theta0 = jnp.asarray([0.5], jnp.float32)
    raw0 = jnp.stack([jnp.log(theta0 / (1 - theta0)),
                      jnp.zeros_like(theta0)], axis=1)
    args = (batch, inten, cum, jnp.zeros((B,), jnp.int32),
            jnp.full((B,), 48, jnp.int32), budget, base_c, ms0,
            jnp.zeros(inten.shape, jnp.float32), raw0)
    lowered = _train.lower(*args, cfg=LearnConfig(steps=4), max_window=48,
                           n_epochs=PROBE_HORIZON)
    return lowered, args


def _lower_fitness_probe():
    from repro.core.solvers import common as solver_common
    batch, _, cums = _probe_batch()
    inst = jax.tree.map(lambda a: a[0], batch)
    cum = cums[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(PROBE_SEED))
    P = 64
    prio = jax.random.normal(k1, (P, inst.T), jnp.float32)
    assign = solver_common.random_allowed_assign(k2, inst, (P,))
    deadline = jnp.int32(PROBE_HORIZON)
    fn = jax.jit(functools.partial(
        solver_common.population_fitness, objective="carbon",
        machine_rule="fixed", sweeps=2, use_kernels=True))
    args = (inst, cum, deadline, prio, assign)
    return fn.lower(*args), args


def _lower_gate_probe():
    from repro.core.solvers.online_jax import dirty_mask
    _, inten, _ = _probe_batch()
    fn = jax.jit(jax.vmap(
        functools.partial(dirty_mask, max_window=48, use_kernels=True),
        in_axes=(0, None, None)))
    args = (inten, jnp.float32(0.4), jnp.int32(48))
    return fn.lower(*args), args


# name -> (entry, builder).  ``entry`` is the dotted path of the function
# the cell actually times — stamped into every BENCH_*.json probe block so
# ``perf_gate --check-provenance`` can fail artifacts whose probes name a
# kernel entry point that no longer exists (benchmark honesty: a probe
# that silently times dead code is worse than no probe).
PROBE_CELLS = {
    "dispatch_sweep": ("repro.core.solvers.online_jax._sweep",
                       _lower_dispatch_probe),
    "learn_step": ("repro.learn.train._train", _lower_learn_probe),
    "fitness_pallas": ("repro.kernels.ops.population_carbon",
                       _lower_fitness_probe),
    "gate_pallas": ("repro.kernels.ops.gate_threshold", _lower_gate_probe),
}


def _probe_cell(build: Callable, timer: BenchTimer) -> dict:
    from repro.launch.hlo_analysis import memory_dict
    lowered, args = build()
    t0 = timer.clock()
    compiled = lowered.compile()
    compile_s = timer.clock() - t0
    warms = [timer.timed(compiled, *args)[1]
             for _ in range(PROBE_WARM_REPS)]
    return {
        "compile_s": round(compile_s, 6),
        # warm_s_min is the gate quantity (noise-robust on shared hosts:
        # the best rep is the program's floor, medians carry OS jitter);
        # the median/all columns stay for reading run-to-run variance.
        "warm_s_min": round(float(np.min(warms)), 6),
        "warm_s_median": round(float(np.median(warms)), 6),
        "warm_s_all": [round(w, 6) for w in warms],
        "memory": memory_dict(compiled),
    }


@functools.lru_cache(maxsize=1)
def _cached_probe() -> dict:
    timer = BenchTimer()
    return {
        "cells": {name: {"entry": entry, **_probe_cell(build, timer)}
                  for name, (entry, build) in PROBE_CELLS.items()},
        "warm_reps": PROBE_WARM_REPS,
        "fingerprint": machine_fingerprint(),
    }


def perf_probe(fresh: bool = False) -> dict:
    """Compile + time the pinned probe cells (cached per process).

    AOT compile is timed apart from ``PROBE_WARM_REPS`` synced warm calls,
    and each cell carries the compiled program's memory analysis.  This
    dict is what benchmarks stamp under ``timing.probe`` and what
    ``benchmarks/perf_gate.py`` compares against stored baselines.
    """
    if fresh:
        _cached_probe.cache_clear()
    return json.loads(json.dumps(_cached_probe()))   # defensive copy


def bench_timing(wall_s: float, probe: bool = True) -> dict:
    """The standard ``timing`` block for a BENCH_*.json record."""
    out = {"wall_s": round(float(wall_s), 3)}
    if probe:
        out["probe"] = perf_probe()
    return out


def run_batch(setup: BenchSetup) -> dict:
    """Solve ``setup.instances`` instances; returns aggregate metrics plus
    the solved ``batch`` and the full host-side ``result``."""
    rng = np.random.default_rng(setup.seed)
    year = synthesize(setup.region, days=366, seed=2024)
    packs, cums = [], []
    pad = setup.n_jobs * setup.k_tasks
    for _ in range(setup.instances):
        inst: Instance = generate_instance(
            rng, n_jobs=setup.n_jobs, k_tasks=setup.k_tasks,
            n_machines=setup.n_machines,
            heterogeneous=setup.heterogeneous)
        packs.append(pack(inst, pad_tasks=pad))
        start = int(rng.integers(0, year.n_epochs - DEF_HORIZON))
        w: CarbonTrace = year.window(start, DEF_HORIZON)
        cums.append(jnp.asarray(w.cumulative()))
    batch = stack_packed(packs)
    cum = jnp.stack(cums)
    keys = jax.random.split(jax.random.key(setup.seed), setup.instances)

    # Explicit sync inside the timed region (async dispatch must not leak
    # past the clock); host-side np conversion happens after it stops.
    res, dt = BenchTimer().timed(
        solve_bilevel_batch, batch, cum, keys, objective=setup.objective,
        stretch=setup.stretch, cfg1=SA_FAST, cfg2=SA_FAST)
    res = jax.tree.map(np.asarray, res)

    return {
        "setup": setup,
        "seconds": dt,
        "opt_makespan": res.opt_makespan,
        "carbon_savings": res.carbon_savings,
        "energy_savings": res.energy_savings,
        "utilization": res.baseline.utilization,
        "baseline_carbon": res.baseline.carbon,
        "optimized_carbon": res.optimized.carbon,
        "baseline_energy": res.baseline.energy,
        "optimized_energy": res.optimized.energy,
        "batch": batch,
        "cum": cum,
        "result": res,
    }


def summarize(r: dict) -> dict:
    return {
        "mean_carbon_savings_pct": 100 * float(r["carbon_savings"].mean()),
        "p10_carbon_savings_pct": 100 * float(
            np.percentile(r["carbon_savings"], 10)),
        "p90_carbon_savings_pct": 100 * float(
            np.percentile(r["carbon_savings"], 90)),
        "mean_energy_savings_pct": 100 * float(r["energy_savings"].mean()),
        "mean_opt_makespan": float(r["opt_makespan"].mean()),
        "mean_utilization_pct": 100 * float(r["utilization"].mean()),
        "seconds": round(r["seconds"], 1),
    }


def is_primary_process() -> bool:
    """True on the rank that owns artifact writes (rank 0; trivially true
    single-process).  Multi-process benchmark results are replicated —
    every rank holds identical values (the bit-exact contract) — so only
    one may write, or concurrent ranks race on the same BENCH_*.json."""
    return jax.process_index() == 0


def write_json(path: str, record: dict) -> str:
    """Write a benchmark record as pretty JSON (e.g. BENCH_online.json).

    Every record is stamped with :func:`provenance` (git SHA, jax/jaxlib,
    device kind/count, process count) unless the caller already provided
    one — no BENCH_*.json leaves the harness untraceable.  On a
    multi-process fleet only rank 0 writes (results are replicated).
    """
    if not is_primary_process():
        return path
    if "provenance" not in record:
        record = {**record, "provenance": provenance()}
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def write_csv(name: str, rows: list[dict]) -> str:
    path = os.path.join(OUT_DIR, f"{name}.csv")
    if not is_primary_process():
        return path
    os.makedirs(OUT_DIR, exist_ok=True)
    if rows:
        keys = list(rows[0])
        with open(path, "w") as f:
            f.write(",".join(keys) + "\n")
            for row in rows:
                f.write(",".join(str(row[k]) for k in keys) + "\n")
    return path
