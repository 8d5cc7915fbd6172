"""Streaming dispatch service: continuous DAG arrivals into a lane pool.

The closed-batch machinery (PR 1-5) answers "given these instances at t=0,
how much carbon can gating save?".  This engine answers the question the
batch sweeps can't: what happens when delaying one job *back-pressures the
queue*.  It is :class:`repro.serve.engine.ServeEngine`'s continuous-batching
shape reused for scheduling instead of decoding:

* a fixed pool of ``n_lanes`` slot lanes, each holding one admitted DAG job
  packed to a static ``(pad_tasks, n_machines)`` shape (free lanes carry
  :func:`repro.scenarios.batching.padding_rows`-style inert padding, so the
  pool arrays never change shape);
* **one jitted gate-and-dispatch step over the whole pool per tick** —
  :func:`repro.core.solvers.online_jax.dispatch_epoch_shared` vmapped over
  lanes (partitioned) or scanned over them in priority order (shared),
  gated by the carbon quantile threshold (day-ahead
  :func:`~repro.core.solvers.online_jax.dirty_mask`, or forecast-banded via
  :func:`repro.forecast.rolling.rolling_dirty_mask` when
  ``forecast_every`` is set);
* admission runs a second jitted program per job (the scheduling analogue
  of serve's prefill): a greedy solve fixes the job's stretch budget and
  its carbon/energy baseline;
* completed jobs are evicted and their lanes refilled from the queue
  (:class:`repro.serve.lanes.LanePool` — the bookkeeping shared with the
  serve engine) — FIFO by default, or shortest-critical-path-first under
  backlog via the admission-policy hook (``admission="scpf"``).

Two fleet modes:

* ``shared_fleet=False`` (default) — each lane is an independent fleet
  partition (the lanes' machines are disjoint), so carbon gating couples
  jobs only through *lane occupancy*: delaying a job keeps its lane busy
  longer and later arrivals queue — the PCAPS-style carbon/latency tension
  the stream benchmark measures.
* ``shared_fleet=True`` — every lane contends for ONE pool-global machine
  set (the paper's common-fleet model): machine free-times are pool state
  threaded through a ``lax.scan`` over lanes in deterministic priority
  order (earliest admission first, rid tie-break), so one lane's placements
  consume machine free-time that later lanes see *within the same epoch*.
  Admission's greedy budget solve also starts from the live shared
  free-times, so stretch deadlines reflect real contention.

Contracts (property- and golden-tested in ``tests/test_stream.py`` /
``tests/test_stream_golden.py``):

* **closed-batch bit-exactness** — with every arrival at t=0 and enough
  lanes, each partitioned-mode job's dispatch decisions (start/assign/
  scheduled and the stretch budget) are bit-exact against the batched
  :func:`~repro.core.solvers.online_jax.online_carbon_gated_jax` path on
  the same instance, across scenario families x fleets (the engine's tick
  *is* that simulator's loop body);
* **determinism** — the whole run is a pure function of the seed: same
  seed, same event log, replay-locked by a tiny golden per fleet mode; the
  shared-fleet step depends only on the lane *priority order*, never on
  which physical lane a job landed in;
* every evicted schedule passes the shared validator
  (:mod:`repro.core.validate`), and shared-fleet evictions additionally
  verify no cross-lane machine overlap against every schedule already
  evicted this run.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
import types
from typing import Mapping, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import validate
from repro.core.carbon import CarbonTrace, sample_window, synthesize
from repro.core.carbon import EPOCHS_PER_DAY
from repro.core.instance import Instance, Job, PackedInstance, pack
from repro.core.solvers.online_jax import (LaneState, dirty_mask,
                                           dispatch_epoch_shared,
                                           downstream_critical_path,
                                           init_lane_state, simulate_online)
from repro.core.objectives import evaluate
from repro.forecast.rolling import rolling_dirty_mask
from repro.obs import MetricsRegistry, Tracer, get_tracer
from repro.scenarios.batching import padding_rows
from repro.scenarios.fleets import build_fleet
from repro.scenarios.generator import ScenarioConfig, sample_job
from repro.serve.lanes import LanePool
from repro.stream.arrivals import sample_arrivals


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """One streaming scenario: traffic shape x job shape x pool x gate."""

    arrivals: str = "poisson"      # arrival family (repro.stream.arrivals)
    rate: float = 0.05             # mean jobs per epoch
    horizon: int = 1024            # stream length (epochs)
    n_lanes: int = 8               # fixed lane-pool size
    family: str = "layered"        # DAG family of the arriving jobs
    width: int = 3
    depth: int = 2
    n_machines: int = 3            # machines per lane partition
    fleet: str = "homog"
    mean_dur: float = 5.0          # exp mean of base task durations
    theta: float = 0.5             # carbon-gate quantile
    window: int = 96               # gate look-ahead window (epochs)
    stretch: float = 1.5           # per-job stretch budget
    machine_rule: str = "earliest_finish"
    region: str = "AU-SA"
    seed: int = 0
    forecast_every: int | None = None   # None: exact day-ahead gate
    forecast_scale: float = 1.0
    forecast_model: str = "oracle_ar1"
    shared_fleet: bool = False     # lanes contend for one machine set
    admission: str = "fifo"        # lane-refill policy (ADMISSION_POLICIES)

    def validate(self) -> "StreamConfig":
        from repro.stream.arrivals import ARRIVAL_NAMES
        if self.arrivals not in ARRIVAL_NAMES:
            raise ValueError(f"unknown arrival family {self.arrivals!r}")
        if self.n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {self.n_lanes}")
        if self.admission not in ADMISSION_POLICIES:
            raise ValueError(f"unknown admission policy {self.admission!r}")
        return self


@dataclasses.dataclass
class StreamJob:
    """Host-side per-job record (the stream analogue of serve.Request)."""

    rid: int
    job: Job                        # job.arrival = stream arrival epoch
    inst: PackedInstance | None = None   # packed at admission (arrival = t)
    admitted: int = -1
    completed: int = -1             # absolute completion epoch
    budget: int = -1                # absolute stretch deadline
    greedy_makespan: int = -1       # absolute greedy completion (baseline)
    greedy_carbon: float = 0.0
    greedy_energy: float = 0.0
    carbon: float = 0.0
    energy: float = 0.0
    finished: bool = False
    truncated: bool = False         # fully placed, completes past the stream
    start: np.ndarray | None = None
    assign: np.ndarray | None = None

    @property
    def arrival(self) -> int:
        return self.job.arrival

    @property
    def queue_delay(self) -> int:
        """Epochs spent waiting for a free lane (-1 if never admitted)."""
        return self.admitted - self.job.arrival if self.admitted >= 0 else -1

    @property
    def carbon_savings(self) -> float:
        """1 - gated/greedy carbon (0 when unfinished or zero baseline)."""
        if not self.finished or self.greedy_carbon <= 0.0:
            return 0.0
        return 1.0 - self.carbon / self.greedy_carbon


# An un-observed histogram's snapshot (summary() placeholder).
_EMPTY_DIST = {"count": 0, "mean": 0.0, "p50": 0.0, "p90": 0.0, "max": 0.0}

# Admission-policy registry: payload-list -> index of the next admit.
# "fifo" is queue order; "scpf" admits the shortest-critical-path job among
# those already arrived (backlog triage: under contention, short jobs clear
# lanes faster) — both deterministic, rid tie-break.
ADMISSION_POLICIES = ("fifo", "scpf")


class StreamResult(NamedTuple):
    jobs: list[StreamJob]          # every stream job, rid order
    events: list[dict]             # serializable event log (golden-locked)
    meta: dict
    # StreamEngine.summary() of the run.  The default is an IMMUTABLE empty
    # mapping: a `summary: dict = {}` default here would be one dict object
    # shared by every StreamResult constructed without a summary, so any
    # in-place mutation of one run's summary would leak into all others
    # (regression-locked in tests/test_stream.py).  Real constructions pass
    # a fresh dict per result (see simulate_stream).
    summary: Mapping = types.MappingProxyType({})


# ---------------------------------------------------------------------------
# Jitted pool programs (module level: engines with equal shapes share them).
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_epochs", "machine_rule"))
def _admission_eval(inst: PackedInstance, cum: jnp.ndarray,
                    stretch: jnp.ndarray, admitted: jnp.ndarray,
                    mfree0: jnp.ndarray, n_epochs: int, machine_rule: str):
    """Per-job admission solve (the scheduling analogue of serve prefill).

    Greedy-dispatches the job alone to fix the absolute stretch deadline
    ``admitted + int(stretch * greedy_relative)`` and the greedy
    carbon/energy baseline the savings metric is measured against.
    ``mfree0`` is the fleet the greedy starts on: all-zeros for a
    partitioned lane (its machines are idle by construction at insert), the
    *live shared free-times* for a shared fleet — so a shared-fleet job's
    deadline and baseline reflect the contention it is actually admitted
    into.  At ``admitted = 0`` on an idle fleet the budget arithmetic is
    bit-identical to
    :func:`~repro.core.solvers.online_jax.online_carbon_gated_jax`'s
    (same float32 cast chain) — part of the closed-batch parity contract.
    """
    state0 = init_lane_state(inst.T).merge(mfree0)
    g = simulate_online(inst, jnp.zeros((n_epochs,), bool), jnp.int32(0),
                        n_epochs=n_epochs, machine_rule=machine_rule,
                        state0=state0)
    obj = evaluate(inst, g.start, g.assign, cum)
    rel = (obj.makespan - admitted).astype(jnp.float32)
    budget = admitted + (jnp.float32(stretch) * rel).astype(jnp.int32)
    complete = jnp.all(g.scheduled | ~inst.task_mask)
    return downstream_critical_path(inst), budget, obj, complete


@functools.partial(jax.jit, static_argnames=("machine_rule",))
def _pool_tick(pool: PackedInstance, cp: jnp.ndarray, lstate: LaneState,
               mfree: jnp.ndarray, dirty: jnp.ndarray, budget: jnp.ndarray,
               t: jnp.ndarray, machine_rule: str):
    """ONE gate-and-dispatch step over the whole lane pool — epoch ``t``,
    partitioned fleets.

    :func:`dispatch_epoch_shared` vmapped over lanes, each with its own
    machine row ``mfree[lane]`` (disjoint partitions: lanes cannot interact
    through machines).  All lanes share the global gate bit ``dirty[t]`` and
    clock ``t``.  Returns the new pool state plus per-lane "all tasks
    placed" flags and completion epochs (the eviction signal).
    """
    dirty_t = dirty[t]
    lstate, mfree = jax.vmap(
        lambda i, c, s, mf, b: dispatch_epoch_shared(
            i, s, mf, dirty_t, b, t, machine_rule=machine_rule, cp=c)
    )(pool, cp, lstate, mfree, budget)
    done = jnp.all(lstate.scheduled | ~pool.task_mask, axis=1)
    comp = jnp.max(jnp.where(pool.task_mask, lstate.comp, 0), axis=1)
    return lstate, mfree, done, comp


@functools.partial(jax.jit, static_argnames=("machine_rule",))
def _pool_tick_shared(pool: PackedInstance, cp: jnp.ndarray,
                      lstate: LaneState, mfree: jnp.ndarray,
                      dirty: jnp.ndarray, budget: jnp.ndarray,
                      t: jnp.ndarray, order: jnp.ndarray, machine_rule: str):
    """ONE gate-and-dispatch step over the lane pool — epoch ``t``, SHARED
    fleet.

    A ``lax.scan`` over lanes in ``order`` (the deterministic priority
    permutation: occupied lanes by (admission epoch, rid), free lanes last)
    threading the single pool-global ``mfree [M]`` through every lane's
    :func:`dispatch_epoch_shared` — so a higher-priority lane's placements
    consume machine free-time that lower-priority lanes see *within this
    same epoch*.  Free (padding) lanes have no real tasks, place nothing,
    and leave ``mfree`` untouched, so scanning them is inert.  The result
    depends on ``order`` only through which *jobs* it ranks — not on which
    physical lane a job occupies (tested as lane-order determinism).
    """
    dirty_t = dirty[t]

    def body(mf, lane):
        inst = jax.tree.map(lambda x: x[lane], pool)
        st = jax.tree.map(lambda x: x[lane], lstate)
        st, mf = dispatch_epoch_shared(inst, st, mf, dirty_t, budget[lane],
                                       t, machine_rule=machine_rule,
                                       cp=cp[lane])
        return mf, st

    mfree, stacked = jax.lax.scan(body, mfree, order)
    # Scatter the scan-ordered rows back to lane order (order is a
    # permutation of 0..L-1).
    lstate = jax.tree.map(lambda x, s: x.at[order].set(s), lstate, stacked)
    done = jnp.all(lstate.scheduled | ~pool.task_mask, axis=1)
    comp = jnp.max(jnp.where(pool.task_mask, lstate.comp, 0), axis=1)
    return lstate, mfree, done, comp


@jax.jit
def _insert_lane(pool: PackedInstance, cp: jnp.ndarray, lstate: LaneState,
                 budget: jnp.ndarray, lane: jnp.ndarray,
                 inst: PackedInstance, job_cp: jnp.ndarray,
                 job_budget: jnp.ndarray):
    """Insert one admitted job into ``lane`` (serve's cache insert, for
    dispatch state): overwrite the lane's instance/cp/budget rows and zero
    its task-side progress.  Machine free-times are NOT touched here — a
    partitioned lane's row is cleared separately (:func:`_clear_lane_mfree`),
    while a shared fleet's global ``mfree`` must survive inserts unchanged
    (the machines stay busy regardless of which job a lane holds)."""
    pool = PackedInstance(*(getattr(pool, f).at[lane].set(getattr(inst, f))
                            for f in PackedInstance._fields))
    lstate = LaneState(*(getattr(lstate, f).at[lane].set(
        jnp.zeros_like(getattr(lstate, f)[lane]))
        for f in LaneState._fields))
    return pool, cp.at[lane].set(job_cp), lstate, budget.at[lane].set(
        job_budget)


@jax.jit
def _clear_lane_mfree(mfree: jnp.ndarray, lane: jnp.ndarray) -> jnp.ndarray:
    """Reset one partitioned lane's machine row to idle (the previous
    occupant completed at or before the insert epoch, so its residual
    free-times are stale by construction)."""
    return mfree.at[lane].set(jnp.zeros_like(mfree[lane]))


@jax.jit
def _eval_schedule(inst: PackedInstance, start: jnp.ndarray,
                   assign: jnp.ndarray, cum: jnp.ndarray):
    return evaluate(inst, start, assign, cum), \
        validate.total_violations(inst, start, assign)


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

class StreamEngine:
    """Long-running lane-pool dispatcher over one carbon trace.

    ``trace`` is the stream's global clock and carbon signal: epoch ``t`` of
    every lane is epoch ``t`` of the trace.  ``pad_tasks`` fixes the static
    task axis (jobs must fit); the fleet (``powers_kw``/``speeds``) is the
    per-lane machine partition.  See the module docstring for semantics and
    contracts.
    """

    def __init__(self, trace: CarbonTrace, powers_kw: Sequence[float],
                 speeds: Sequence[float], n_lanes: int, pad_tasks: int, *,
                 theta: float = 0.5, window: int = 96, stretch: float = 1.5,
                 machine_rule: str = "earliest_finish",
                 forecast_every: int | None = None,
                 forecast_scale: float = 1.0,
                 forecast_model: str = "oracle_ar1", seed: int = 0,
                 validate_evictions: bool = True,
                 shared_fleet: bool = False, admission: str = "fifo",
                 tracer: Tracer | None = None,
                 metrics: MetricsRegistry | None = None):
        if machine_rule not in ("earliest_finish", "min_energy"):
            raise ValueError(f"unknown machine_rule {machine_rule!r}")
        if admission not in ADMISSION_POLICIES:
            raise ValueError(f"unknown admission policy {admission!r}")
        # Telemetry is host-side only (bit-exact contract: repro.obs).  The
        # ambient tracer resolves to a no-op unless REPRO_TRACE=1 or a
        # global tracer is installed; metrics are always on (cheap Python
        # around an already-synchronous host loop) and feed summary().
        self.tracer = tracer if tracer is not None else get_tracer()
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._wall_seen: set[str] = set()
        self.forecast_every = forecast_every
        self.trace = trace
        self.powers = tuple(float(p) for p in powers_kw)
        self.speeds = tuple(float(s) for s in speeds)
        self.T, self.M = int(pad_tasks), len(self.powers)
        self.E = trace.n_epochs
        self.stretch = float(stretch)
        self.machine_rule = machine_rule
        self.validate_evictions = bool(validate_evictions)
        self.shared_fleet = bool(shared_fleet)
        self.admission = admission
        self._cp_cache: dict[int, int] = {}   # rid -> critical path (scpf)
        intensity = jnp.asarray(trace.intensity)
        self.cum = jnp.asarray(trace.cumulative())
        if forecast_every is None:
            # Exact day-ahead gate: identical thresholds to the batched path.
            self.dirty = dirty_mask(intensity, jnp.float32(theta),
                                    jnp.int32(window),
                                    max_window=int(window))
        else:
            # Forecast-banded gate: thresholds re-quantiled from rolling
            # imperfect forecasts (scale=0 reproduces the day-ahead gate).
            self.dirty = rolling_dirty_mask(
                intensity, jnp.float32(theta), jnp.int32(window),
                jax.random.key(seed), jnp.float32(forecast_scale),
                every=int(forecast_every), max_window=int(window),
                model=forecast_model)
        # Host copies for telemetry reads (the arrays are computed either
        # way on the first tick; pulling them here changes nothing).
        self._dirty_host = np.asarray(self.dirty)
        self._intensity_host = np.asarray(trace.intensity)
        self.pool = LanePool(n_lanes)
        self._reset_pool_state()

    def _reset_pool_state(self) -> None:
        L, T, M = self.pool.n_lanes, self.T, self.M
        self.pool_inst = padding_rows(L, T, M)      # inert free lanes
        self.lstate = LaneState(
            jnp.zeros((L, T), bool), jnp.zeros((L, T), jnp.int32),
            jnp.zeros((L, T), jnp.int32), jnp.zeros((L, T), jnp.int32))
        # Machine free-times: pool-global [M] when the fleet is shared,
        # one disjoint partition row per lane [L, M] otherwise.
        self.mfree = jnp.zeros((M,) if self.shared_fleet else (L, M),
                               jnp.int32)
        self.cp = jnp.zeros((L, T), jnp.int32)
        self.budget = jnp.zeros((L,), jnp.int32)
        self._done = np.zeros(L, bool)
        self._comp = np.zeros(L, np.int64)
        # Shared-fleet eviction validation: per-machine (start, end, rid)
        # intervals of every schedule evicted this run.
        self._fleet_busy: list[list[tuple[int, int, int]]] = \
            [[] for _ in range(M)]

    # -- admission / eviction -------------------------------------------------

    def _admit_job(self, lane: int, sj: StreamJob, t: int) -> bool:
        job = dataclasses.replace(sj.job, arrival=t)   # can't start pre-lane
        inst = pack(Instance(jobs=(job,), powers_kw=self.powers,
                             speeds=self.speeds), pad_tasks=self.T)
        # The greedy budget solve's starting fleet: idle for a partitioned
        # lane (its machines are free at insert by construction), the LIVE
        # shared free-times otherwise — a shared-fleet job's stretch
        # deadline and savings baseline are measured against what greedy
        # could do on the fleet it actually contends for.
        mfree0 = (self.mfree if self.shared_fleet
                  else jnp.zeros((self.M,), jnp.int32))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("stream.admission"):
            cp, budget, obj, complete = _admission_eval(
                inst, self.cum, jnp.float32(self.stretch), jnp.int32(t),
                mfree0, n_epochs=self.E, machine_rule=self.machine_rule)
            complete = bool(complete)  # host sync: the admission solve ran
        self._observe_wall("admission_wall_s", time.perf_counter() - t0)
        if not complete:
            # Too late even greedily: reject instead of wedging the lane.
            # The job surfaces with admitted == -1 / finished == False.
            self.metrics.counter("jobs_rejected").inc()
            self.tracer.instant("reject", t, rid=sj.rid,
                                arrival=int(sj.arrival))
            return False
        self.pool_inst, self.cp, self.lstate, self.budget = _insert_lane(
            self.pool_inst, self.cp, self.lstate, self.budget,
            jnp.int32(lane), inst, cp, budget)
        if not self.shared_fleet:
            self.mfree = _clear_lane_mfree(self.mfree, jnp.int32(lane))
        sj.inst = inst
        sj.admitted = t
        sj.budget = int(budget)
        sj.greedy_makespan = int(obj.makespan)
        sj.greedy_carbon = float(obj.carbon)
        sj.greedy_energy = float(obj.energy)
        self.metrics.counter("jobs_admitted").inc()
        self.metrics.histogram("queue_delay_epochs").observe(sj.queue_delay)
        self.tracer.instant(
            "admit", t, rid=sj.rid, lane=lane, arrival=int(sj.arrival),
            queue_delay=int(sj.queue_delay), budget=int(sj.budget),
            carbon_gpkwh=round(float(self._intensity_host[t]), 3))
        return True

    def _finish(self, lane: int, sj: StreamJob,
                truncated: bool = False) -> None:
        with jax.profiler.TraceAnnotation("stream.eviction"):
            self.pool.evict(lane)
            row = jax.tree.map(lambda x: x[lane], self.lstate)
            obj, viol = _eval_schedule(sj.inst, row.start, row.assign,
                                       self.cum)
            if self.validate_evictions and int(viol) != 0:
                raise AssertionError(
                    f"evicted job rid={sj.rid} has an infeasible schedule "
                    f"(violation mass {int(viol)})")
            if self.shared_fleet and self.validate_evictions:
                self._check_fleet_overlap(sj, np.asarray(row.start),
                                          np.asarray(row.assign))
            sj.completed = int(self._comp[lane])
            sj.carbon = float(obj.carbon)
            sj.energy = float(obj.energy)
            sj.start = np.asarray(row.start)
            sj.assign = np.asarray(row.assign)
            sj.finished = True
            sj.truncated = bool(truncated)
            self.metrics.counter("jobs_completed").inc()
            if truncated:
                self.metrics.counter("jobs_truncated").inc()
            self.metrics.histogram("carbon_savings_pct").observe(
                100.0 * sj.carbon_savings)
            if self.tracer.enabled:
                self.tracer.span(f"job:{sj.rid}", sj.admitted, sj.completed,
                                 lane=lane, rid=sj.rid,
                                 carbon_g=round(sj.carbon, 3),
                                 greedy_carbon_g=round(sj.greedy_carbon, 3),
                                 savings_pct=round(100 * sj.carbon_savings, 2))
                self.tracer.instant("evict", sj.completed, rid=sj.rid,
                                    lane=lane, truncated=sj.truncated)

    def _check_fleet_overlap(self, sj: StreamJob, start: np.ndarray,
                             assign: np.ndarray) -> None:
        """Shared-fleet eviction invariant: no task of this schedule may
        overlap, on its machine, any task of a schedule already evicted this
        run.  Per-lane validation can't see this (each lane's validator only
        knows its own job); the threaded ``mfree`` makes it hold by
        construction, and this check keeps it honest."""
        dur = np.asarray(sj.inst.dur)
        for ti in np.nonzero(np.asarray(sj.inst.task_mask))[0]:
            m = int(assign[ti])
            s = int(start[ti])
            e = s + int(dur[ti, m])
            for (bs, be, brid) in self._fleet_busy[m]:
                if s < be and bs < e:
                    raise AssertionError(
                        f"shared-fleet overlap: rid={sj.rid} task {ti} "
                        f"[{s}, {e}) collides with rid={brid} "
                        f"[{bs}, {be}) on machine {m}")
            self._fleet_busy[m].append((s, e, sj.rid))

    # -- admission policy / lane priority -------------------------------------

    def _job_critical_path(self, sj: StreamJob) -> int:
        """Base-duration critical path of a job's DAG (machine-independent —
        the scpf admission key; cached per rid)."""
        got = self._cp_cache.get(sj.rid)
        if got is not None:
            return got
        job = sj.job
        cp = list(job.base_durations)
        succ: list[list[int]] = [[] for _ in range(job.n_tasks)]
        for u, v in job.edges:
            succ[u].append(v)
        for u in range(job.n_tasks - 1, -1, -1):
            if succ[u]:
                cp[u] = job.base_durations[u] + max(cp[v] for v in succ[u])
        val = max(cp, default=0)
        self._cp_cache[sj.rid] = val
        return val

    def _admission_select(self):
        """The LanePool ``select`` hook for the configured policy (None ==
        FIFO, the O(1) deque pop)."""
        if self.admission == "fifo":
            return None
        return lambda ready: min(
            range(len(ready)),
            key=lambda i: (self._job_critical_path(ready[i]), ready[i].rid))

    def _lane_order(self) -> jnp.ndarray:
        """Deterministic shared-fleet priority permutation for this tick:
        occupied lanes by (admission epoch, rid) — earliest-admitted job wins
        machine contention — then free lanes (inert in the scan)."""
        occ = sorted((sj.admitted, sj.rid, lane)
                     for lane, sj in self.pool.active())
        order = [lane for _, _, lane in occ] + self.pool.free_lanes()
        return jnp.asarray(order, jnp.int32)

    # -- telemetry ------------------------------------------------------------

    def _observe_wall(self, name: str, seconds: float) -> None:
        """Wall-clock split: the first call per name within a run lands in
        the ``*_first`` histogram (jit compile + execute — or a warm hit on
        the process-wide jit cache), later calls in ``*_warm``."""
        first = name not in self._wall_seen
        self._wall_seen.add(name)
        suffix = "_first" if first else "_warm"
        self.metrics.histogram(name + suffix).observe(seconds)

    def _trace_tick(self, t: int, queue: list) -> None:
        """Per-tick trace samples (guarded: zero work when tracing is off)."""
        active = sum(1 for _ in self.pool.active())
        dirty = bool(self._dirty_host[t])
        self.tracer.counter("gate", t, 1.0 if dirty else 0.0)
        self.tracer.counter("carbon_gpkwh", t,
                            float(self._intensity_host[t]))
        self.tracer.counter("lanes_active", t, active)
        self.tracer.counter("queue_len", t, sum(
            1 for s in queue if s.job.arrival <= t))
        if dirty and any(not self._done[lane]
                         for lane, _ in self.pool.active()):
            # The gate is closed while admitted work is still unplaced —
            # this epoch's ready tasks are (budget permitting) deferred.
            self.tracer.instant("gate_defer", t)
        if self.forecast_every is not None and t % self.forecast_every == 0:
            # Forecast re-quantile boundary: the rolling gate's thresholds
            # from here on were re-solved with epoch-t information.
            self.tracer.instant("forecast_resolve", t)

    def summary(self) -> dict:
        """Aggregate view of the last ``run`` from the metrics registry:
        job counts, the queue-delay and savings distributions, final lane
        occupancy, and the jit-compile vs warm wall-clock split."""
        snap = self.metrics.snapshot()
        return {
            "jobs_admitted": snap.get("jobs_admitted", 0),
            "jobs_rejected": snap.get("jobs_rejected", 0),
            "jobs_completed": snap.get("jobs_completed", 0),
            "jobs_truncated": snap.get("jobs_truncated", 0),
            "queue_delay_epochs": snap.get(
                "queue_delay_epochs", dict(_EMPTY_DIST)),
            "carbon_savings_pct": snap.get(
                "carbon_savings_pct", dict(_EMPTY_DIST)),
            "final_lane_occupancy": snap.get("final_lane_occupancy", 0),
            "gate_closed_epochs": snap.get("gate_closed_epochs", 0),
            "ticks": snap.get("ticks", 0),
            "wall": {k: v for k, v in snap.items()
                     if k.startswith(("tick_wall_s", "admission_wall_s"))},
        }

    # -- main loop ------------------------------------------------------------

    def run(self, jobs: Sequence[Job]) -> list[StreamJob]:
        """Serve a finite stream of jobs; returns one StreamJob per input
        (rid = input index), finished or flagged ``finished=False``.

        The pool is drained before returning, so back-to-back ``run`` calls
        on one engine are independent (the serve-engine re-entry contract).
        Per-run telemetry accumulates in ``self.metrics`` (reset on entry;
        read it through :meth:`summary`) and, when tracing is enabled, in
        ``self.tracer``.
        """
        for j in jobs:
            if j.n_tasks > self.T:
                raise ValueError(f"job with {j.n_tasks} tasks exceeds "
                                 f"pad_tasks={self.T}")
        self.metrics.reset()
        self._wall_seen: set[str] = set()
        sjobs = [StreamJob(rid=i, job=j) for i, j in enumerate(jobs)]
        # deque: the FIFO head pop in LanePool.admit is O(1) — with a plain
        # list every admission under backlog shifted the whole queue (the
        # O(n^2) fix, regression-locked in tests/test_serve.py).
        queue = collections.deque(
            sorted(sjobs, key=lambda s: (s.job.arrival, s.rid)))
        select = self._admission_select()
        t = 0
        while t < self.E - 1:
            # 1. evict lanes whose job finished executing by epoch t
            for lane, sj in list(self.pool.active()):
                if self._done[lane] and self._comp[lane] <= t:
                    self._finish(lane, sj)
            # 2. admit arrived jobs into the freed lanes (FIFO, or the
            #    configured policy over the ready prefix); jobs too close to
            #    the trace end to finish even greedily are rejected (they
            #    surface finished=False rather than wedging a lane)
            for lane, sj in self.pool.admit(
                    queue, ready=lambda s: s.job.arrival <= t,
                    select=select):
                if not self._admit_job(lane, sj, t):
                    self.pool.evict(lane)
                    sj.admitted = -1
            # 3. idle fast-forward: empty pool, next arrival in the future
            if not self.pool.any_active():
                if not queue:
                    break
                t = max(t + 1, int(queue[0].job.arrival))
                continue
            # 4. ONE jitted gate-and-dispatch step over the whole pool
            if self.tracer.enabled:
                self._trace_tick(t, queue)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("stream.tick"):
                if self.shared_fleet:
                    self.lstate, self.mfree, done, comp = _pool_tick_shared(
                        self.pool_inst, self.cp, self.lstate, self.mfree,
                        self.dirty, self.budget, jnp.int32(t),
                        self._lane_order(), machine_rule=self.machine_rule)
                else:
                    self.lstate, self.mfree, done, comp = _pool_tick(
                        self.pool_inst, self.cp, self.lstate, self.mfree,
                        self.dirty, self.budget, jnp.int32(t),
                        machine_rule=self.machine_rule)
                self._done, self._comp = np.asarray(done), np.asarray(comp)
            self._observe_wall("tick_wall_s", time.perf_counter() - t0)
            self.metrics.counter("ticks").inc()
            if self._dirty_host[t]:
                self.metrics.counter("gate_closed_epochs").inc()
            t += 1
        # End-of-stream surfacing: any lane whose job is fully placed gets
        # its stats, including those whose completion epoch lands PAST the
        # final tick — those evict with truncated=True (the silent-drop fix:
        # a feasible, fully-dispatched schedule used to surface as
        # finished=False with no carbon/savings stats just because the trace
        # ended before its last task ran out).
        for lane, sj in list(self.pool.active()):
            if self._done[lane]:
                self._finish(lane, sj,
                             truncated=bool(self._comp[lane] > t))
        self.metrics.gauge("final_lane_occupancy").set(
            sum(1 for _ in self.pool.active()))
        # drain: unfinished jobs surface flagged; the pool resets so the
        # engine is re-entrant (never re-dispatches stale lanes)
        self.pool.drain()
        self._reset_pool_state()
        return sjobs


# ---------------------------------------------------------------------------
# Scenario-level entry points.
# ---------------------------------------------------------------------------

def sample_stream_jobs(rng: np.random.Generator,
                       cfg: StreamConfig) -> list[Job]:
    """One DAG job per arrival: arrival epochs from the configured arrival
    family, DAG + durations from the scenario generator's job sampler."""
    cfg.validate()
    arrivals = sample_arrivals(cfg.arrivals, rng, cfg.rate, cfg.horizon)
    scen = ScenarioConfig(family=cfg.family, n_jobs=1, width=cfg.width,
                          depth=cfg.depth, n_machines=cfg.n_machines,
                          fleet=cfg.fleet, mean_dur=cfg.mean_dur).validate()
    return [dataclasses.replace(sample_job(rng, scen), arrival=int(a))
            for a in arrivals]


def event_log(jobs: Sequence[StreamJob]) -> list[dict]:
    """Serializable per-job event records, rid order — the replay artifact
    the golden test locks (same seed -> identical log)."""
    out = []
    for sj in sorted(jobs, key=lambda s: s.rid):
        ev = {
            "rid": sj.rid,
            "arrival": int(sj.arrival),
            "admitted": int(sj.admitted),
            "queue_delay": int(sj.queue_delay),
            "finished": bool(sj.finished),
        }
        if sj.admitted >= 0:
            ev.update({
                "budget": int(sj.budget),
                "greedy_makespan": int(sj.greedy_makespan),
                "greedy_carbon_g": round(float(sj.greedy_carbon), 3),
            })
        if sj.finished:
            ev.update({
                "completed": int(sj.completed),
                "carbon_g": round(float(sj.carbon), 3),
                "energy_kwh": round(float(sj.energy), 4),
                "carbon_savings_pct": round(100 * sj.carbon_savings, 3),
            })
        if sj.truncated:
            # Conditional so pre-existing goldens (all jobs complete within
            # the stream) stay byte-identical.
            ev["truncated"] = True
        out.append(ev)
    return out


def simulate_stream(cfg: StreamConfig,
                    jobs: Sequence[Job] | None = None,
                    tracer: Tracer | None = None) -> StreamResult:
    """Run one streaming scenario end to end, deterministically.

    Everything derives from ``cfg.seed``: the arrival times, the job DAGs
    and durations, the fleet, and the carbon window (drawn from a
    synthesized year through :func:`repro.core.carbon.sample_window` — the
    path whose off-by-one fix makes the final window reachable).  ``jobs``
    overrides the sampled stream (the closed-batch parity tests inject
    arrival-at-0 jobs this way).  ``tracer`` (or ``REPRO_TRACE=1``)
    captures the run's event timeline — host-side only, bit-exact with
    tracing off.
    """
    cfg.validate()
    rng = np.random.default_rng(cfg.seed)
    if jobs is None:
        jobs = sample_stream_jobs(rng, cfg)
    powers, speeds = build_fleet(cfg.fleet, rng, cfg.n_machines)
    # Arrivals land in [0, horizon); the trace runs two days past it so
    # late arrivals (and stretch-delayed tails) have room to finish.
    n_epochs = cfg.horizon + 2 * EPOCHS_PER_DAY
    days = -(-n_epochs // EPOCHS_PER_DAY) + 2
    year = synthesize(cfg.region, days=days, seed=cfg.seed)
    trace = sample_window(year, rng, n_epochs)
    pad_tasks = max((j.n_tasks for j in jobs), default=1)
    eng = StreamEngine(trace, powers, speeds, cfg.n_lanes, pad_tasks,
                       theta=cfg.theta, window=cfg.window,
                       stretch=cfg.stretch, machine_rule=cfg.machine_rule,
                       forecast_every=cfg.forecast_every,
                       forecast_scale=cfg.forecast_scale,
                       forecast_model=cfg.forecast_model, seed=cfg.seed,
                       shared_fleet=cfg.shared_fleet,
                       admission=cfg.admission, tracer=tracer)
    sjobs = eng.run(jobs)
    meta = {
        "config": {k: (v if v is None or isinstance(v, (int, float, str,
                                                        bool)) else str(v))
                   for k, v in dataclasses.asdict(cfg).items()},
        "n_jobs": len(sjobs),
        "n_finished": sum(sj.finished for sj in sjobs),
        "pad_tasks": pad_tasks,
        "n_epochs": trace.n_epochs,
    }
    return StreamResult(sjobs, event_log(sjobs), meta, eng.summary())
