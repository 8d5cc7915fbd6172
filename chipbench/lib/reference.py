"""Plain references the benchmark holds the scheduler's answers against.

Straightforward numpy and Python over the benchmark's own inputs
(:mod:`gen`); nothing here imports the scheduler or reads anything it made
except the answers under test.

* :func:`violations` and :func:`objectives` -- the feasibility
  constraints of the paper's model (arrivals, DAG precedence, no overlap
  on a machine, a deadline) and its objectives, makespan, energy and
  carbon (Defs. 2.1-2.3), in float64.
* :func:`timing_sweep` -- the carbon-greedy timing pass over a fixed
  sequence, the point the bound's phase-2 search starts from.
* :func:`gate` -- the online carbon gate: an epoch is dirty when its
  intensity lies above the ``theta``-quantile (linear interpolation) of
  the next ``window`` epochs, in the float32 arithmetic the configuration
  states.
* :func:`dispatch` -- the online gate-and-dispatch policy for one job from
  its admission epoch: each epoch, in task-index order, every arrived task
  whose predecessors have completed, that the gate does not hold back (it
  may wait only while ``t + 1 + critical path <= budget``) and that finds
  a free machine starts there, on the free machine of least duration, then
  least energy, then lowest index.
* :func:`stream` -- a whole stream through a pool of partitioned lanes:
  FIFO admission into free lanes, a greedy solve at admission that fixes
  the job's stretch budget (rejecting a job that cannot finish before the
  trace ends), eviction when a job's last task has completed.
"""
from __future__ import annotations

import collections
import dataclasses

import numpy as np

from gen import EPOCH_HOURS


@dataclasses.dataclass(frozen=True)
class Tasks:
    """One instance: ``T`` tasks on ``M`` machines (every machine allowed)."""

    dur: np.ndarray        # int64 [T, M] epochs
    preds: tuple           # preds[t]: tuple of predecessor task indices
    arrival: np.ndarray    # int64 [T]
    power: np.ndarray      # float64 [M] kW

    @property
    def T(self) -> int:   # noqa: N802
        return self.dur.shape[0]

    @property
    def M(self) -> int:   # noqa: N802
        return self.dur.shape[1]


def make_tasks(jobs, powers, speeds) -> Tasks:
    """Flatten jobs ``(arrival, base_durations, edges)`` into one instance."""
    from gen import durations
    rows, preds, arr = [], [], []
    for arrival, base, edges in jobs:
        t0 = len(preds)
        k = len(base)
        local = [[] for _ in range(k)]
        for u, v in edges:
            local[v].append(t0 + u)
        rows.append(durations(base, speeds))
        preds.extend(tuple(p) for p in local)
        arr.extend([arrival] * k)
    return Tasks(np.concatenate(rows), tuple(preds),
                 np.asarray(arr, np.int64), np.asarray(powers, np.float64))


def cumulative(intensity: np.ndarray) -> np.ndarray:
    """float64 ``cum[e]``, gCO2 per kW up to epoch ``e``; length E+1."""
    cum = np.zeros(intensity.shape[0] + 1, dtype=np.float64)
    np.cumsum(intensity.astype(np.float64) * EPOCH_HOURS, out=cum[1:])
    return cum


def objectives(tasks: Tasks, start, assign, cum: np.ndarray):
    """(makespan, energy kWh, carbon g) of a schedule; starts and ends are
    clipped to the trace as the scheduler's model clips them."""
    start = np.asarray(start, np.int64)
    assign = np.asarray(assign, np.int64)
    d = tasks.dur[np.arange(tasks.T), assign]
    p = tasks.power[assign]
    e = cum.shape[0] - 1
    s0 = np.clip(start, 0, e)
    s1 = np.clip(start + d, 0, e)
    return (int((start + d).max()), float((p * d * EPOCH_HOURS).sum()),
            float((p * (cum[s1] - cum[s0])).sum()))


def violations(tasks: Tasks, start, assign, deadline=None) -> int:
    """Number of violated constraints (0 == feasible)."""
    start = np.asarray(start, np.int64)
    assign = np.asarray(assign, np.int64)
    if start.shape != (tasks.T,) or assign.shape != (tasks.T,):
        return tasks.T
    if (assign < 0).any() or (assign >= tasks.M).any():
        return int(((assign < 0) | (assign >= tasks.M)).sum())
    comp = start + tasks.dur[np.arange(tasks.T), assign]
    bad = int((start < tasks.arrival).sum())
    for t, ps in enumerate(tasks.preds):
        bad += sum(1 for u in ps if start[t] < comp[u])
    for m in range(tasks.M):
        on = np.flatnonzero(assign == m)
        order = on[np.argsort(start[on], kind="stable")]
        bad += int((start[order[1:]] < comp[order[:-1]]).sum())
    if deadline is not None:
        bad += int((comp > deadline).sum())
    return bad


def critical_path(tasks: Tasks) -> np.ndarray:
    """Least-duration path from each task to the end of its job, itself
    included."""
    dmin = tasks.dur.min(axis=1)
    succ = [[] for _ in range(tasks.T)]
    for v, ps in enumerate(tasks.preds):
        for u in ps:
            succ[u].append(v)
    cp = np.zeros(tasks.T, np.int64)
    for t in range(tasks.T - 1, -1, -1):
        cp[t] = dmin[t] + max((cp[v] for v in succ[t]), default=0)
    return cp


def gate(intensity: np.ndarray, theta: float, window: int) -> np.ndarray:
    """``dirty[t]``: intensity above the ``theta``-quantile of
    ``intensity[t : t + window]`` (shorter at the trace's end), in the
    float32 arithmetic the configuration states."""
    dtype = np.float32
    x = intensity.astype(dtype)
    E = x.shape[0]
    th = dtype(theta)
    dirty = np.zeros(E, bool)
    for t in range(E):
        w = np.sort(x[t:t + window])
        n = w.shape[0]
        vi = th * dtype(n - 1)
        lo = np.floor(vi)
        gamma = dtype(vi - lo)
        a = w[int(lo)]
        b = w[min(int(lo) + 1, n - 1)]
        diff = dtype(b - a)
        if gamma >= 0.5:
            q = dtype(b - dtype(diff * dtype(1 - gamma)))
        else:
            q = dtype(a + dtype(diff * gamma))
        dirty[t] = x[t] > q
    return dirty


def dispatch(tasks: Tasks, cp, dirty, budget: int, t0: int, n_epochs: int):
    """The gated (or, with ``dirty`` all False, greedy) online schedule of
    one job admitted at ``t0`` onto idle machines, stepping epochs
    ``t0 .. n_epochs - 2``.  Returns (start, assign, placed-all, epoch of
    the last placement)."""
    T, M = tasks.T, tasks.M
    dur = tasks.dur.tolist()
    energy = (tasks.power[None, :] * tasks.dur).tolist()
    preds = tasks.preds
    placed = [False] * T
    comp = [0] * T
    start = [0] * T
    assign = [0] * T
    mfree = [0] * M
    left = T
    last = -1
    t = t0
    while left and t < n_epochs - 1:
        hold = bool(dirty[t])
        ready = [k for k in range(T)
                 if not placed[k] and tasks.arrival[k] <= t
                 and all(placed[u] and comp[u] <= t for u in preds[k])
                 and not (hold and t + 1 + cp[k] <= budget)]
        for k in ready:
            free = [m for m in range(M) if mfree[m] <= t]
            if not free:
                break
            m = min(free, key=lambda m: (dur[k][m], energy[k][m], m))
            placed[k] = True
            start[k], assign[k] = t, m
            comp[k] = mfree[m] = t + dur[k][m]
            left -= 1
            last = t
        t += 1
    return (np.asarray(start, np.int64), np.asarray(assign, np.int64),
            left == 0, last)


@dataclasses.dataclass
class JobResult:
    """What the stream did with one job, as the scheduler reports it."""

    admitted: int = -1
    budget: int = -1
    greedy_makespan: int = -1
    greedy_carbon: float = 0.0
    finished: bool = False
    truncated: bool = False
    completed: int = -1
    carbon: float = 0.0
    energy: float = 0.0
    start: np.ndarray | None = None
    assign: np.ndarray | None = None


def stream(jobs, powers, speeds, intensity, *, n_lanes: int, theta: float,
           window: int, stretch: float, dtype=np.float32):
    """Serve ``jobs`` (``(arrival, base, edges)``, rid order) through
    ``n_lanes`` partitioned lanes over the trace ``intensity``; returns one
    :class:`JobResult` per job.  ``dtype`` is the precision the trace and
    its running sums are held in: float32, as the configuration states,
    or a lower one for the control (the carbon is then summed from a
    trace rounded to it)."""
    E = intensity.shape[0]
    x = intensity.astype(dtype).astype(np.float32)
    cum = cumulative(x).astype(dtype).astype(np.float64)
    dirty = gate(x, theta, window)
    never = np.zeros(E, bool)
    out = [JobResult() for _ in jobs]
    queue = collections.deque(sorted(range(len(jobs)),
                                     key=lambda r: (jobs[r][0], r)))
    lanes = [None] * n_lanes     # (rid, last placement epoch or None, end)
    f32_stretch = np.float32(stretch)

    def finish(rid, task, s, a, truncated):
        r = out[rid]
        ms, en, cb = objectives(task, s, a, cum)
        r.finished, r.truncated = True, truncated
        r.completed, r.energy, r.carbon = ms, en, cb
        r.start, r.assign = s, a

    held = {}
    t = 0
    while t < E - 1:
        for lane, slot in enumerate(lanes):
            if slot is not None and slot[1] is not None and slot[1] < t \
                    and slot[2] <= t:
                rid = slot[0]
                finish(rid, *held.pop(rid), truncated=False)
                lanes[lane] = None
        for lane in [i for i, s in enumerate(lanes) if s is None]:
            if not queue or jobs[queue[0]][0] > t:
                break
            rid = queue.popleft()
            _, base, edges = jobs[rid]
            task = make_tasks([(t, base, edges)], powers, speeds)
            cp = critical_path(task)
            gs, ga, complete, _ = dispatch(task, cp, never, 0, t, E)
            if not complete:
                continue
            gms, _, gcb = objectives(task, gs, ga, cum)
            budget = t + int(f32_stretch * np.float32(gms - t))
            s, a, done, last = dispatch(task, cp, dirty, budget, t, E)
            r = out[rid]
            r.admitted, r.budget = t, budget
            r.greedy_makespan, r.greedy_carbon = gms, gcb
            end = int((s + task.dur[np.arange(task.T), a]).max())
            lanes[lane] = (rid, last if done else None, end)
            held[rid] = (task, s, a)
        if all(s is None for s in lanes):
            if not queue:
                break
            t = max(t + 1, jobs[queue[0]][0])
            continue
        t += 1
    for slot in lanes:
        if slot is not None and slot[1] is not None:
            finish(slot[0], *held.pop(slot[0]), truncated=slot[2] > t)
    return out


def timing_sweep(tasks: Tasks, start, assign, cum: np.ndarray,
                 deadline: int, sweeps: int = 2) -> np.ndarray:
    """The carbon-greedy timing pass over a fixed sequence: in descending
    start order, each task moves to the start within its slack (before
    its successors, before the next task on its machine, ending by the
    deadline) where its own emissions are least, earliest among equals."""
    T = tasks.T
    start = np.asarray(start, np.int64).copy()
    assign = np.asarray(assign, np.int64)
    d = tasks.dur[np.arange(T), assign]
    H = cum.shape[0] - 1
    succ = [[] for _ in range(T)]
    for v, ps in enumerate(tasks.preds):
        for u in ps:
            succ[u].append(v)
    for _ in range(sweeps):
        key = start * T + np.arange(T)
        for t in np.argsort(-key, kind="stable"):
            cap = min([start[v] for v in succ[t]]
                      + [start[v] for v in np.flatnonzero(
                          (assign == assign[t]) & (key > key[t]))]
                      + [deadline])
            lo, hi = start[t], cap - d[t]
            if hi < lo:
                continue
            s = np.arange(lo, hi + 1)
            cost = cum[np.minimum(s + d[t], H)] - cum[np.minimum(s, H)]
            start[t] = lo + int(np.argmin(cost))
    return start
