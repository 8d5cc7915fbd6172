"""Stream cells: ``StreamEngine.run`` replaying whole job streams.

Set-up draws the run's carbon trace and a pool of streams from the seed,
builds one engine over the trace (lanes, machines and ``pad_tasks`` fixed
by the configuration) and serves one short stream through it, which fills
every lane, so that each program the window calls is compiled.  The
window replays the streams back to back through that engine.  Every
job of every stream is held against the plain reference
(:func:`reference.stream`).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

import gen
import reference
import window

STREAMS = 8      # streams drawn per run; the window cycles through them
# A traced run records a slice of its first stream: from 3 s in, for 2 s.
# A whole stream's trace (every op of every admission's epoch loop) takes
# minutes to write out and to read.
TRACE_PART = (3.0, 2.0)
WALL = ("tick_wall_s_first", "tick_wall_s_warm",
        "admission_wall_s_first", "admission_wall_s_warm")


@dataclasses.dataclass
class State:
    cfg: dict
    traffic: dict
    engine: object
    intensity: np.ndarray
    powers: tuple
    speeds: tuple
    streams: list          # per stream: list of (arrival, base, edges)
    program_jobs: list     # the same streams as the scheduler's Job objects
    units: list = None


def draw(cfg: dict, traffic: dict, seed: int):
    """The run's trace and streams: a pure function of the seed.

    Stream ``i`` has the same arrival epochs and the same jobs for every
    seed (drawn from stream ``i`` of seed 0); the seed draws the carbon
    trace and the order in which the jobs take the arrival epochs.  Below
    capacity every seed gives the engine the same jobs to admit and place.
    Under a backlog it does not: the order decides which jobs are still
    queued when the trace ends, and a rejected job costs less than an
    admitted one."""
    n_epochs = cfg["horizon"] + cfg["tail_days"] * gen.EPOCHS_PER_DAY
    days = math.ceil(n_epochs / gen.EPOCHS_PER_DAY) + 2
    year = gen.synthesize(cfg["region"], days, seed)
    rng = gen.seed_rng(seed, 0)
    start = int(rng.integers(0, year.shape[0] - n_epochs + 1))
    intensity = gen.window(year, start, n_epochs)
    streams = []
    for i in range(STREAMS):
        base = gen.seed_rng(0, 1, i)
        times = gen.arrivals(traffic["arrivals"], base, traffic["jobs"],
                             cfg["horizon"],
                             traffic.get("mean_burst", 4.0))
        jobs = [gen.stream_job(base, cfg["width"], cfg["depth"],
                               cfg["mean_dur"]) for _ in times]
        order = gen.seed_rng(seed, 1, i).permutation(len(jobs))
        streams.append([(int(a), *jobs[k]) for a, k in zip(times, order)])
    return intensity, streams


def setup(cell, seed: int, say) -> State:
    from repro.core.carbon import CarbonTrace
    from repro.core.instance import Job
    from repro.stream.engine import StreamEngine

    cfg, traffic = cell.config, cell.traffic
    intensity, streams = draw(cfg, traffic, seed)
    powers, speeds = gen.fleet(cfg["fleet"], cfg["machines"])
    program_jobs = [[Job(arrival=a, base_durations=b, edges=e)
                     for a, b, e in s] for s in streams]
    engine = StreamEngine(
        CarbonTrace(cfg["region"], intensity), powers, speeds,
        n_lanes=cfg["lanes"], pad_tasks=cfg["width"] * cfg["depth"],
        theta=cfg["theta"], window=cfg["window"], stretch=cfg["stretch"],
        machine_rule=cfg["machine_rule"], admission=cfg["admission"],
        shared_fleet=cfg["shared_fleet"])
    warm = [dataclasses.replace(j, arrival=0)
            for j in program_jobs[0][:cfg["lanes"] + 1]]
    engine.run(warm)
    say(f"stream: {len(streams)} streams of {traffic['jobs']} jobs, "
        f"{intensity.shape[0]} epochs, {cfg['lanes']} lanes x "
        f"{cfg['machines']} machines")
    return State(cfg, traffic, engine, intensity, powers, speeds, streams,
                 program_jobs)


def window_run(state: State, seconds: float, traced=None) -> list:
    eng = state.engine

    def step(i):
        out = eng.run(state.program_jobs[i])
        return len(out), (out, {k: list(eng.metrics.histogram(k).samples)
                                for k in WALL})

    state.units = window.run(step, len(state.streams), seconds,
                             min_units=2 if traced else 1, traced=traced)
    return state.units


def _samples(units, prefix):
    return [x for u in units for k, v in u.out[1].items()
            if k.startswith(prefix) for x in v]


def end_to_end(state: State) -> dict:
    """Jobs per second and the admission tail."""
    units = state.units
    return {"stream_jobs_per_s": window.rate(units),
            "stream_admit_p95_ms":
                1e3 * window.percentile(_samples(units, "admission"), 95)}


def spans(state: State) -> dict:
    """The engine's own synced timers over the window's untraced streams."""
    units = [u for u in state.units if not u.traced]
    return {"tick_s": _samples(units, "tick"),
            "admission_s": _samples(units, "admission"),
            "window_s": sum(u.end - u.start for u in units)}


def release(state: State) -> None:
    state.engine = None


def compare(results, refs, job_lens):
    """Per job: (its decisions or exact numbers differ, carbon gap) between
    the scheduler's records and the reference's.  Decisions: admission
    epoch, budget, greedy makespan, finished and truncated flags,
    completion, each task's start and machine; the energy, exact in the
    configuration's float32, with them."""
    rows = []
    for sj, r, k in zip(results, refs, job_lens):
        bad = (sj.admitted != r.admitted or sj.finished != r.finished
               or (r.admitted >= 0 and (sj.budget != r.budget
                                        or sj.greedy_makespan
                                        != r.greedy_makespan)))
        gaps = []
        if r.admitted >= 0 and sj.admitted >= 0:
            gaps.append(_gap(sj.greedy_carbon, r.greedy_carbon))
        if r.finished and sj.finished:
            bad |= (sj.truncated != r.truncated
                    or sj.completed != r.completed
                    or float(sj.energy) != r.energy
                    or not np.array_equal(np.asarray(sj.start)[:k], r.start)
                    or not np.array_equal(np.asarray(sj.assign)[:k],
                                          r.assign))
            gaps.append(_gap(sj.carbon, r.carbon))
        rows.append((bool(bad), max(gaps, default=0.0)))
    return rows


def _gap(x, ref):
    return abs(float(x) - ref) / max(abs(ref), 1e-30)


def reference_of(state: State, index: int, dtype=np.float32):
    cfg = state.cfg
    return reference.stream(state.streams[index], state.powers,
                            state.speeds, state.intensity,
                            n_lanes=cfg["lanes"], theta=cfg["theta"],
                            window=cfg["window"], stretch=cfg["stretch"],
                            dtype=dtype)


def check(state: State, limits: dict):
    """(attempted, failed, [(name, value, limit)]) over every job of every
    stream in the window."""
    refs = {}
    rows = []
    for u in state.units:
        if u.index not in refs:
            refs[u.index] = reference_of(state, u.index)
        lens = [len(b) for _, b, _ in state.streams[u.index]]
        rows += compare(u.out[0], refs[u.index], lens)
    return judge(rows, limits)


def judge(rows, limits):
    failed = sum(1 for r in rows if r[0] or r[1] > limits["carbon_rel_gap"])
    return len(rows), failed, [
        ("jobs_mismatched", sum(r[0] for r in rows),
         limits["jobs_mismatched"]),
        ("carbon_rel_gap", max((r[1] for r in rows), default=0.0),
         limits["carbon_rel_gap"])]


def control_rows(state: State):
    """The control: the reference itself, with its trace and running sums
    in bfloat16, put in the scheduler's place over the window's streams."""
    import ml_dtypes
    rows = []
    for u in state.units:
        lens = [len(b) for _, b, _ in state.streams[u.index]]
        rows += compare(reference_of(state, u.index, ml_dtypes.bfloat16),
                        reference_of(state, u.index), lens)
    return rows
