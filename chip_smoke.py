"""Bring-up smoke: the scheduler's main path, once, on a TPU.

    python chip_smoke.py              # one chip: kernels, stream, offline bound
    python chip_smoke.py --chips 4    # four chips: the sharded structure sweep

One chip runs five phases in one process, each through the entry points a
user calls, at the sizes users run:

(a) device check — JAX must see a TPU; there is no CPU fallback;
(b) both Pallas kernels compiled for the chip (``interpret=False``) at real
    widths, against the jnp path on the same chip: ``population_carbon``
    at 96 candidates x 40 tasks over a 1500-epoch window and over a
    366-day trace, ``gate_threshold`` at 1216 epochs x 96-epoch windows;
(c) the stream engine at ``benchmarks.stream_serve.FULL`` widths under
    Poisson arrivals at load 0.9, in both fleet modes, every finished
    schedule validated, plus a closed batch of 8 jobs at t=0 that must
    equal the numpy oracle ``online_carbon_gated`` exactly;
(d) the offline bound on the paper's batch (1000 instances of 10 jobs x
    4 tasks on 5 machines, 1500-epoch windows) via ``run_batch``, every
    schedule validated and a sample cross-checked on the host;
(e) the bound on the paper's 10-server fleet (32 instances, 400 start-cost
    rows each): every row the timing sweep selects on the device equals
    numpy's f32 row bit for bit, and in ``run_batch``'s own solve no more
    instances than ``bound.paper_m10`` allows end less than 1% below the
    numpy sweep of their baseline.  Both numbers are in the result line.

``--chips 4`` runs only the shard layer: the full ``structure_sweep`` grid
on 4 devices and on 1, compared row for row, and the device of each shard
of the per-device bound dispatch.

Any failed check raises; nothing is caught.  Each line names the device;
the last line of stdout is the JSON result.  The compile cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
sys.path.append(os.path.join(ROOT, "chipbench", "lib"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 2024
STREAM_LOAD = 0.9
CLOSED_BATCH = 8
N_CROSSCHECK = 16
POP, TASKS = 96, 40
PAPER_INSTANCES = 1000


def require_tpu(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found "
                         f"{len(devs)} TPU device(s)")
    return devs


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Phase:
    """Times one phase; reports wall and compile seconds on exit."""

    def __init__(self, say, clock: CompileClock, name: str):
        self.say, self.clock, self.name = say, clock, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0, self.h0 = self.clock.seconds, self.clock.cache_hits
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            self.say(f"{self.name}: wall {time.perf_counter() - self.t0}s, "
                     f"compile {self.clock.seconds - self.c0}s, "
                     f"compile-cache hits "
                     f"{self.clock.cache_hits - self.h0}")
        return False


def _max_abs(a, b) -> float:
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return float(d.max()) if d.size else 0.0


# ---------------------------------------------------------------------------
# (b) Kernels at real widths, compiled, against the jnp path on the chip.
# ---------------------------------------------------------------------------

def phase_kernels(say) -> None:
    from repro.core import generate_instance, pack, synthesize
    from repro.core.objectives import carbon, task_durations
    from repro.core.solvers.common import random_allowed_assign
    from repro.core.solvers.online_jax import (quantile_threshold,
                                               sorted_windows)
    from repro.kernels import ops
    from repro.kernels.schedule_eval import schedule_delta_pallas

    rng = np.random.default_rng(SEED)
    inst = pack(generate_instance(rng, n_jobs=10, k_tasks=4, n_machines=5),
                pad_tasks=TASKS)
    year = synthesize("AU-SA", days=366, seed=SEED)
    # Reordering a sum of T positive f32 terms moves it by at most
    # (T-1) * 2^-24 of itself: the kernel path's power-weighted reduction
    # is XLA's, the deltas under it are exact.
    rtol = TASKS * 2.0 ** -24
    deltas = jax.jit(functools.partial(schedule_delta_pallas,
                                       interpret=False))
    carbon_kernel = jax.jit(functools.partial(ops.population_carbon,
                                              interpret=False))
    carbon_jnp = jax.jit(jax.vmap(carbon, in_axes=(None, 0, 0, None)))

    @jax.jit
    def deltas_jnp(start, dur, cum):
        h = cum.shape[0] - 1
        return cum[jnp.clip(start + dur, 0, h)] - cum[jnp.clip(start, 0, h)]

    for label, horizon in (("1500-epoch window", 1500),
                           ("366-day trace", year.n_epochs)):
        cum = jnp.asarray(year.window(int(rng.integers(0, year.n_epochs)),
                                      horizon).cumulative())
        k1, k2 = jax.random.split(jax.random.key(int(rng.integers(1 << 30))))
        starts = jax.random.randint(k1, (POP, TASKS), -4, horizon + 4,
                                    jnp.int32)
        assigns = random_allowed_assign(k2, inst, (POP,))
        dur = jax.vmap(lambda a: task_durations(inst, a))(assigns)
        d_k, d_j = deltas(starts, dur, cum), deltas_jnp(starts, dur, cum)
        c_k = carbon_kernel(inst, starts, assigns, cum)
        c_j = carbon_jnp(inst, starts, assigns, cum)
        exact = bool(jnp.array_equal(d_k, d_j))
        err = jnp.abs(c_k - c_j)
        within = bool(jnp.all(err <= rtol * jnp.abs(c_j)))
        rel = float(jnp.max(err / jnp.maximum(jnp.abs(c_j), 1e-30)))
        say(f"kernel schedule_eval {POP}x{TASKS} over {label} "
            f"(H={horizon}): deltas equal jnp: {exact} (max |diff| "
            f"{_max_abs(d_k, d_j)}); carbon max rel diff {rel} "
            f"(tolerance {rtol})")
        if not exact:
            raise AssertionError(f"schedule_eval deltas != jnp ({label})")
        if not within:
            raise AssertionError(f"population_carbon rel diff {rel} > "
                                 f"{rtol} ({label})")

    n_epochs, max_window = 1216, 96
    inten = jnp.asarray(year.window(int(rng.integers(0, year.n_epochs)),
                                    n_epochs).intensity)
    thr_kernel = jax.jit(functools.partial(ops.gate_threshold,
                                           max_window=max_window,
                                           interpret=False))

    @jax.jit
    def thr_jnp(intensity, theta, window):
        sv, n = sorted_windows(intensity, window, max_window)
        return quantile_threshold(sv, n, theta)

    theta_vec = jnp.asarray(rng.uniform(0.05, 0.95, n_epochs), jnp.float32)
    for theta, window, label in ((jnp.float32(0.5), 96, "theta 0.5"),
                                 (theta_vec, 61, "per-epoch theta")):
        t_k = thr_kernel(inten, theta, jnp.int32(window))
        t_j = thr_jnp(inten, theta, jnp.int32(window))
        exact = bool(jnp.array_equal(t_k, t_j))
        say(f"kernel gate_quantile E={n_epochs} W={max_window} "
            f"window={window} {label}: thresholds equal jnp: {exact} "
            f"(max |diff| {_max_abs(t_k, t_j)})")
        if not exact:
            raise AssertionError(f"gate thresholds != jnp ({label})")


# ---------------------------------------------------------------------------
# (c) The stream engine at stream_serve.FULL widths.
# ---------------------------------------------------------------------------

def phase_stream(say) -> None:
    from benchmarks.stream_serve import FULL, probe_service_epochs
    from repro.core.carbon import sample_window, synthesize
    from repro.core.instance import Instance, pack
    from repro.core.solvers.online import online_carbon_gated
    from repro.core.validate import total_violations
    from repro.scenarios.fleets import build_fleet
    from repro.scenarios.generator import ScenarioConfig, sample_job
    from repro.stream import StreamConfig, StreamEngine, simulate_stream

    knobs = {k: v for k, v in FULL.items() if k not in ("loads", "families")}
    service = probe_service_epochs(knobs, SEED)
    rate = STREAM_LOAD * knobs["n_lanes"] / service
    say(f"stream: FULL widths {knobs}, poisson load {STREAM_LOAD} "
        f"(rate {rate} jobs/epoch, greedy service {service} epochs)")
    viol = jax.jit(total_violations)
    for shared in (False, True):
        mode = "shared" if shared else "partitioned"
        cfg = StreamConfig(arrivals="poisson", rate=rate, seed=SEED,
                           shared_fleet=shared, **knobs)
        t0 = time.perf_counter()
        res = simulate_stream(cfg)
        wall = time.perf_counter() - t0
        done = [sj for sj in res.jobs if sj.finished]
        bad = [sj.rid for sj in done
               if int(viol(sj.inst, jnp.asarray(sj.start),
                           jnp.asarray(sj.assign))) != 0]
        s = res.summary
        say(f"stream {mode}: {len(done)}/{len(res.jobs)} jobs finished "
            f"({s['jobs_rejected']} rejected) in {wall}s wall incl. "
            f"compile, {len(done) / wall} jobs/s incl. compile, mean savings "
            f"{s['carbon_savings_pct']['mean']}%, queue delay p90 "
            f"{s['queue_delay_epochs']['p90']} epochs, violations in "
            f"{len(bad)} schedules")
        if not done:
            raise AssertionError(f"stream {mode}: no job finished")
        if bad:
            raise AssertionError(f"stream {mode}: infeasible rids {bad}")

    # Closed batch: every arrival at t=0, one lane per job — each lane's
    # schedule is the single-instance gated dispatch, so the numpy oracle
    # must agree exactly.
    rng = np.random.default_rng(SEED)
    scen = ScenarioConfig(family=knobs["family"], n_jobs=1,
                          width=knobs["width"], depth=knobs["depth"],
                          n_machines=knobs["n_machines"],
                          fleet=knobs["fleet"],
                          mean_dur=knobs["mean_dur"]).validate()
    jobs = [dataclasses.replace(sample_job(rng, scen), arrival=0)
            for _ in range(CLOSED_BATCH)]
    powers, speeds = build_fleet(knobs["fleet"], rng, knobs["n_machines"])
    trace = sample_window(synthesize("AU-SA", days=30, seed=SEED), rng,
                          knobs["horizon"])
    pad = max(j.n_tasks for j in jobs)
    cfg = StreamConfig()
    eng = StreamEngine(trace, powers, speeds, n_lanes=CLOSED_BATCH,
                       pad_tasks=pad, theta=cfg.theta, window=cfg.window,
                       stretch=cfg.stretch)
    mismatched = []
    for sj in eng.run(jobs):
        inst = pack(Instance(jobs=(sj.job,), powers_kw=powers,
                             speeds=speeds), pad_tasks=pad)
        start, assign = online_carbon_gated(inst, trace.intensity,
                                            theta=cfg.theta,
                                            window=cfg.window,
                                            stretch=cfg.stretch)
        if not (sj.finished and np.array_equal(sj.start, start)
                and np.array_equal(sj.assign, assign)):
            mismatched.append(sj.rid)
    say(f"stream closed batch of {CLOSED_BATCH} jobs at t=0 vs numpy "
        f"oracle: {CLOSED_BATCH - len(mismatched)}/{CLOSED_BATCH} equal "
        f"in (start, assign)")
    if mismatched:
        raise AssertionError(f"closed batch != numpy oracle: {mismatched}")


# ---------------------------------------------------------------------------
# (d) The offline bound on the paper's batch.
# ---------------------------------------------------------------------------

def phase_offline(say) -> None:
    from benchmarks.common import BenchSetup, run_batch, summarize
    from repro.core.validate import check_feasible_np, total_violations_batch

    setup = BenchSetup(instances=PAPER_INSTANCES)
    r = run_batch(setup)
    res, batch = r["result"], r["batch"]
    v_base = np.asarray(total_violations_batch(
        batch, res.baseline.start, res.baseline.assign))
    v_opt = np.asarray(total_violations_batch(
        batch, res.optimized.start, res.optimized.assign, res.deadline))
    late = int((res.optimized.makespan > res.deadline).sum())
    s = summarize(r)
    say(f"offline bound: {setup.instances} instances ({setup.n_jobs} jobs "
        f"x {setup.k_tasks} tasks, {setup.n_machines} machines) in "
        f"{r['seconds']}s wall incl. compile, "
        f"{setup.instances / r['seconds']} instances/s; mean carbon "
        f"savings {s['mean_carbon_savings_pct']}%; infeasible baseline "
        f"{int((v_base != 0).sum())}, optimized {int((v_opt != 0).sum())}, "
        f"past deadline {late}")
    if (v_base != 0).any() or (v_opt != 0).any() or late:
        raise AssertionError("offline bound produced infeasible schedules")

    picks = np.random.default_rng(SEED).choice(
        setup.instances, min(N_CROSSCHECK, setup.instances), replace=False)
    for i in picks:
        inst = jax.tree.map(lambda a: a[i], batch)
        probs = (check_feasible_np(inst, res.baseline.start[i],
                                   res.baseline.assign[i])
                 + check_feasible_np(inst, res.optimized.start[i],
                                     res.optimized.assign[i],
                                     deadline=int(res.deadline[i])))
        if probs:
            raise AssertionError(f"instance {i}: {probs}")
    say(f"offline bound: {len(picks)} sampled instances feasible under "
        f"the host checker check_feasible_np")


# ---------------------------------------------------------------------------
# (e) The paper's 10-server fleet: the sweep's rows and phase 2's gain.
# ---------------------------------------------------------------------------

def phase_paper_m10(say) -> dict:
    """The bound at M=10 (400 start-cost rows per instance), two checks.

    The table build: every row the sweep selects from a table built on
    the device equals the f32 row numpy computes from the same trace, bit
    for bit.  The solve: in ``run_batch``'s own compiled program, no more
    of the batch's instances than the cell allows end less than its
    ``search_gain`` below the numpy sweep of their baseline.  The second
    sees what the first cannot: a fault in the solver's own compiled
    program (on a TPU v5e, before the table was one array, the sweep's
    argmins came out 0 for the first half of each 8-instance block).
    """
    from benchmarks.common import SA_FAST, BenchSetup, run_batch
    from repro.core.decoder import _select_row, sweep_table
    import reference

    with open(os.path.join(ROOT, "chipbench", "configs",
                           "paper_fjsp_m10.json")) as f:
        cfg = json.load(f)
    setup = BenchSetup(instances=cfg["instances"], n_machines=cfg["machines"])
    r = run_batch(setup)
    res, batch = r["result"], r["batch"]
    cum = np.asarray(r["cum"])
    B, T, M = batch.dur.shape
    H = cum.shape[1] - 1

    @jax.jit
    def rows_of(inst, c):
        table = sweep_table(inst, c)
        return jax.vmap(lambda j: _select_row(table, j))(jnp.arange(T * M))

    rows = np.asarray(jax.vmap(rows_of)(batch, jnp.asarray(cum)))
    svec = np.arange(H + 1)
    dur = np.asarray(batch.dur).reshape(B, T * M)
    unequal = sum(int((rows[b] != cum[b][np.minimum(svec + dur[b][:, None],
                                                    H)] - cum[b][svec]
                       ).any(axis=1).sum()) for b in range(B))
    say(f"paper M=10: {B} instances x {T * M} rows of {H + 1} epochs "
        f"selected on the device; rows unlike numpy's f32 rows: {unequal}")

    gains = []
    for i in range(B):
        pred = np.asarray(batch.pred[i])
        tasks = reference.Tasks(
            np.asarray(batch.dur[i], np.int64),
            tuple(tuple(np.flatnonzero(pred[t])) for t in range(T)),
            np.asarray(batch.arrival[i], np.int64),
            np.asarray(batch.power[i], np.float64))
        cum64 = cum[i].astype(np.float64)
        base = (res.baseline.start[i], res.baseline.assign[i])
        swept = reference.timing_sweep(tasks, *base, cum64,
                                       int(res.deadline[i]), SA_FAST.sweeps)
        c_swept = reference.objectives(tasks, swept, base[1], cum64)[2]
        c_opt = reference.objectives(tasks, res.optimized.start[i],
                                     res.optimized.assign[i], cum64)[2]
        gains.append(1.0 - c_opt / c_swept)
    unimproved = [i for i, g in enumerate(gains) if g < cfg["search_gain"]]
    share = len(unimproved) / B
    say(f"paper M=10: phase 2 against the numpy sweep of each baseline: "
        f"least gain {100 * min(gains)}%, mean {100 * np.mean(gains)}%; "
        f"unimproved {unimproved}, share {share} (limit "
        f"{cfg['limits']['unimproved_share']})")
    if unequal:
        raise AssertionError(f"{unequal} M=10 sweep rows differ from numpy")
    if share > cfg["limits"]["unimproved_share"]:
        raise AssertionError(f"M=10: phase 2 left instances {unimproved} "
                             f"unimproved, share {share}")
    return {"m10_rows_unequal": unequal, "m10_unimproved_share": share,
            "m10_least_gain": min(gains)}


# ---------------------------------------------------------------------------
# --chips 4: the shard layer.
# ---------------------------------------------------------------------------

def phase_shards(say, chips: int) -> None:
    from benchmarks.structure_sweep import make_spec
    from repro.scenarios import sweep_structure
    from repro.scenarios.sweep import build_batch
    from repro.shard.sweep import bilevel_shards

    spec = make_spec()
    t0 = time.perf_counter()
    rows_n, _ = sweep_structure(spec, devices=chips)
    wall_n = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_1, _ = sweep_structure(spec, devices=1)
    wall_1 = time.perf_counter() - t0
    differ = [i for i, (a, b) in enumerate(zip(rows_n, rows_1)) if a != b]
    say(f"structure sweep, full grid ({len(rows_1)} cells x "
        f"{spec.instances_per_cell} instances): {chips} devices "
        f"{wall_n}s, 1 device {wall_1}s (both incl. compile); rows equal: "
        f"{len(rows_1) - len(differ)}/{len(rows_1)}")
    for i in differ:
        keys = sorted(k for k in rows_1[i] if rows_1[i][k] != rows_n[i].get(k))
        say(f"  row {i} differs in {keys}")

    # The sweep's own bound dispatch again, to see where each shard ran.
    sb = build_batch(spec)
    keys = jax.random.split(jax.random.key(spec.seed), sb.cum.shape[0])
    shards = bilevel_shards(sb.batch, sb.cum, keys, devices=chips,
                            objective="carbon", stretch=spec.offline_stretch,
                            cfg1=spec.sa, cfg2=spec.sa)
    placed = [sorted({str(d) for leaf in jax.tree.leaves(sh)
                      for d in leaf.devices()}) for sh in shards]
    for i, devs in enumerate(placed):
        say(f"bilevel_shards: shard {i} on {devs}")
    if len(rows_n) != len(rows_1) or differ:
        raise AssertionError(f"{len(differ)} rows differ between {chips} "
                             f"devices and 1")
    want = [[str(d)] for d in jax.devices()[:chips]]
    if placed != want:
        raise AssertionError(f"shards placed on {placed}, want {want}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded structure sweep phase")
    args = ap.parse_args()

    devs = require_tpu(args.chips)
    kind = devs[0].device_kind
    tag = f"[{devs[0].platform} {kind} x{args.chips}]"

    def say(msg: str) -> None:
        print(f"chip_smoke {tag} {msg}", flush=True)

    from benchmarks.common import use_compile_cache
    say(f"jax {jax.__version__}, {len(devs)} device(s) visible, compile "
        f"cache {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    result = {"ok": True, "device": {"platform": devs[0].platform,
                                     "kind": kind, "count": len(devs)}}
    if args.chips == 1:
        with Phase(say, clock, "(b) kernels"):
            phase_kernels(say)
        with Phase(say, clock, "(c) stream engine"):
            phase_stream(say)
        with Phase(say, clock, "(d) offline bound"):
            phase_offline(say)
        with Phase(say, clock, "(e) paper M=10"):
            result["paper_m10"] = phase_paper_m10(say)
    else:
        with Phase(say, clock, "shard layer"):
            phase_shards(say, args.chips)
    say(f"all phases passed in {time.perf_counter() - t0}s wall, "
        f"{clock.seconds}s of it compiling")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
