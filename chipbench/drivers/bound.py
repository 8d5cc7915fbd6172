"""Bound cells: the paper's bi-level bound (``solve_bilevel_batch``) on
whole batches of instances.

Set-up draws a pool of batches of the paper's instances from the seed,
hands them to the scheduler's packer, and compiles the batch program for
their one shape ahead of time.  The window solves batches back to back,
each ending with its result on the host.  Every schedule of every
instance solved in the window is held against the plain reference: both
phases' schedules are feasible (the optimized one within ``S x OPT``), and
the makespan, energy and carbon the scheduler reports equal the
reference's for that schedule.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import gen
import reference
import window

BATCHES = 5       # batches drawn per run; the window cycles through them


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    powers: tuple
    speeds: tuple
    instances: list       # per batch: list of (jobs, intensity window)
    args: list            # per batch: the scheduler's (batch, cum, keys)
    solve: object
    units: list = None


def draw(cfg: dict, seed: int):
    """Per batch, per instance: the jobs and the carbon window."""
    year = gen.synthesize(cfg["region"], cfg["trace_days"], seed)
    h = cfg["horizon"]
    batches = []
    for b in range(BATCHES):
        rng = gen.seed_rng(seed, 2, b)
        insts = []
        for _ in range(cfg["instances"]):
            jobs = [gen.paper_job(rng, cfg["tasks_per_job"],
                                  cfg["mean_dur"], cfg["arrival_horizon"])
                    for _ in range(cfg["jobs"])]
            start = int(rng.integers(0, year.shape[0] - h))
            insts.append((jobs, gen.window(year, start, h)))
        batches.append((insts, int(rng.integers(0, 1 << 31))))
    return batches


def setup(cell, seed: int, say) -> State:
    import jax
    import jax.numpy as jnp
    from repro.core.instance import Instance, Job, pack, stack_packed
    from repro.core.solvers import solve_bilevel_batch
    from repro.core.solvers.annealing import SAConfig

    cfg, traffic = cell.config, cell.traffic
    powers, speeds = gen.fleet(cfg["fleet"], cfg["machines"])
    pad = cfg["jobs"] * cfg["tasks_per_job"]
    instances, args = [], []
    for insts, key in draw(cfg, seed):
        packed = [pack(Instance(jobs=tuple(Job(a, b, e) for a, b, e in jobs),
                                powers_kw=powers, speeds=speeds),
                       pad_tasks=pad) for jobs, _ in insts]
        cum = np.stack([gen.cumulative_f32(w) for _, w in insts])
        keys = jax.random.split(jax.random.key(key), len(insts))
        args.append((stack_packed(packed), jnp.asarray(cum), keys))
        instances.append(insts)
    sa = SAConfig(pop=cfg["sa"]["pop"], iters=cfg["sa"]["iters"],
                  sweeps=cfg["sa"]["sweeps"])
    fn = jax.jit(functools.partial(
        solve_bilevel_batch, objective=traffic["objective"],
        stretch=traffic["stretch"], cfg1=sa, cfg2=sa))
    solve = fn.lower(*args[0]).compile()
    say(f"bound: {BATCHES} batches of {cfg['instances']} "
        f"instances ({cfg['jobs']} jobs x {cfg['tasks_per_job']} tasks, "
        f"{cfg['machines']} machines, {cfg['horizon']} epochs)")
    return State(cfg, traffic, powers, speeds, instances, args, solve)


def window_run(state: State, seconds: float, traced=None) -> list:
    import jax

    def step(i):
        res = jax.device_get(state.solve(*state.args[i]))
        return len(state.instances[i]), res

    state.units = window.run(step, len(state.args), seconds, traced=traced)
    return state.units


def end_to_end(state: State) -> dict:
    return {"bound_instances_per_s": window.rate(state.units)}


def spans(state: State) -> dict:
    units = [u for u in state.units if not u.traced]
    return {"batch_s": [u.end - u.start for u in units],
            "window_s": sum(u.end - u.start for u in units)}


def release(state: State) -> None:
    state.solve = None
    state.args = None


def instance_rows(state: State, index: int, res, control: bool = False):
    """Per instance of one solved batch: (infeasible schedules, reported
    numbers that differ, carbon gap, search left it unimproved).

    Reported numbers: OPT, the deadline, each phase's makespan and energy,
    all exact in the configuration's float32.  Unimproved: the optimized
    schedule's carbon lies less than ``search_gain`` below what the
    reference's timing sweep makes of the baseline schedule, the point
    phase 2's search starts from.

    ``control`` puts the reference in the scheduler's place for phase 2:
    its answer is its own timing sweep of the baseline, and every number
    it reports comes from a trace and running sums in bfloat16."""
    stretch = np.float32(state.traffic["stretch"])
    floor = 1.0 - state.cfg["search_gain"]
    sweeps = state.cfg["sa"]["sweeps"]
    rows = []
    for i, (jobs, inten) in enumerate(state.instances[index]):
        tasks = reference.make_tasks(jobs, state.powers, state.speeds)
        cum = reference.cumulative(inten)
        base = (res.baseline.start[i], res.baseline.assign[i])
        opt_ms = reference.objectives(tasks, *base, cum)[0]
        deadline = int(np.floor(stretch * np.float32(opt_ms)
                                + np.float32(1e-6)))
        c_swept = reference.objectives(
            tasks, reference.timing_sweep(tasks, *base, cum, deadline,
                                          sweeps), base[1], cum)[2]
        if control:
            low = _bf16_cum(inten)
            opt = (reference.timing_sweep(tasks, *base, low, deadline,
                                          sweeps), base[1])
            said = [reference.objectives(tasks, *base, low),
                    reference.objectives(tasks, *opt, low)]
            said_opt, said_deadline = said[0][0], deadline
        else:
            opt = (res.optimized.start[i], res.optimized.assign[i])
            said = [(int(r.makespan[i]), float(r.energy[i]),
                     float(r.carbon[i]))
                    for r in (res.baseline, res.optimized)]
            said_opt, said_deadline = (int(res.opt_makespan[i]),
                                       int(res.deadline[i]))
        bad = ((reference.violations(tasks, *base) != 0)
               + (reference.violations(tasks, *opt, deadline) != 0))
        mism = (said_opt != opt_ms) + (said_deadline != deadline)
        cgap = 0.0
        for sched, (ms, en, cb) in zip((base, opt), said):
            ref_ms, ref_en, ref_cb = reference.objectives(tasks, *sched, cum)
            mism += (ms != ref_ms) + (en != ref_en)
            cgap = max(cgap, _gap(cb, ref_cb))
        c_opt = reference.objectives(tasks, *opt, cum)[2]
        rows.append((bad, mism, cgap, c_opt > floor * c_swept))
    return rows


def _bf16_cum(inten):
    import ml_dtypes
    x = inten.astype(ml_dtypes.bfloat16).astype(np.float32)
    return reference.cumulative(x).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def _gap(x, ref):
    return abs(float(x) - ref) / max(abs(ref), 1e-30)


def check(state: State, limits: dict):
    rows = []
    for u in state.units:
        rows += instance_rows(state, u.index, u.out)
    return judge(rows, limits)


def judge(rows, limits):
    failed = sum(1 for r in rows
                 if r[0] or r[1] or r[2] > limits["carbon_rel_gap"])
    return len(rows), failed, [
        ("schedules_infeasible", sum(r[0] for r in rows),
         limits["schedules_infeasible"]),
        ("reported_mismatched", sum(r[1] for r in rows),
         limits["reported_mismatched"]),
        ("carbon_rel_gap", max(r[2] for r in rows), limits["carbon_rel_gap"]),
        ("unimproved_share", sum(r[3] for r in rows) / len(rows),
         limits["unimproved_share"])]


def control_rows(state: State):
    """The control's rows over the window's batches (see
    :func:`instance_rows`)."""
    rows = []
    for u in state.units:
        rows += instance_rows(state, u.index, u.out, control=True)
    return rows
