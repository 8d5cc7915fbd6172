"""Rolling-horizon (MPC-style) replanning over the batched solvers.

The bi-level solver (:mod:`repro.core.solvers.bilevel`) plans once against a
perfect trace.  This module re-plans: at every boundary ``r_k = k * every``
it re-issues the carbon forecast for the remaining horizon
(:func:`repro.forecast.models.issue` at ``t0 = r_k``), freezes every task
that has already *started* executing under the incumbent plan, and re-runs
the SA search on the remaining sub-DAG against the updated forecast — model
predictive control with the paper's phase-2 search as the per-step
controller.  The whole replan sequence is one ``lax.scan`` (one XLA
program), and :func:`solve_mpc_batch` vmaps it over instances x forecast
seeds — including mixed-shape scenario batches padded by
:func:`repro.scenarios.batching.pack_aligned` (the freeze transform
preserves the padding contract: padded tasks are never frozen because they
never "start", and padded machines stay disallowed since ``_frozen_instance``
only ever *shrinks* ``allowed`` for frozen real tasks).

Freezing without changing the SGS decoder
-----------------------------------------
A started task cannot move (its start is in the past) nor migrate (it is
running).  Both are enforced by an *instance transform* plus a *candidate
projection*, so the stock SGS/SA machinery is reused unchanged:

* ``arrival``: frozen tasks get ``arrival = start`` (pinning the earliest
  start at the executed start), free tasks get ``arrival = max(arrival,
  r_k)`` (nothing can start in the past);
* ``allowed``: frozen tasks shrink to the one machine they run on, so every
  SA proposal keeps them there;
* priorities: frozen tasks are projected into a high band
  (``FROZEN_BAND - start``) so SGS places them first, in executed-start
  order.  Earliest-feasible placement then reproduces the executed prefix
  *exactly*: arrival pins the lower bound, and the incumbent's feasibility
  guarantees machines and predecessors impose nothing later.
* the timing sweep gets the ``frozen`` mask and never shifts a frozen task
  (``decode_full(..., frozen=...)``).

Every replan keeps the incumbent plan as a warm start *and* as a fallback
(the incumbent stays feasible for the transformed instance because free
tasks start at ``>= r_k`` by construction), so planned carbon under the
current forecast is monotone non-increasing across a replan — with a perfect
forecast (``scale = 0``) realized carbon can only improve on the day-ahead
plan.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.instance import EPOCH_HOURS, PackedInstance
from repro.core.objectives import evaluate, utilization
from repro.core.solvers import common
from repro.core.solvers.annealing import SAConfig, solve_sa
from repro.forecast import models as fmodels

NO_DEADLINE = jnp.int32(1 << 27)

# Frozen tasks live this far above any free candidate priority (free prios
# are clamped to FREE_CEIL), so SGS always places the executed prefix first,
# in executed-start order.  Both bounds are small powers of two: every
# integer in [FROZEN_BAND - 2^20, FROZEN_BAND] is exactly representable in
# float32, so ``FROZEN_BAND - start`` keeps *distinct* priorities for
# distinct starts (a 1e9-style band would collapse them — ulp(1e9) = 64 —
# and place frozen tasks in index order, breaking the prefix).
FROZEN_BAND = jnp.float32(2 ** 21)
FREE_CEIL = jnp.float32(2 ** 19)


class MPCConfig(NamedTuple):
    """Static knobs of the rolling replanner (hashable; jit-static)."""

    every: int = 48                  # replan interval (epochs)
    n_replans: int = 4               # boundaries 0, every, ..., (n-1)*every
    stretch: float = 1.5             # deadline = floor(stretch * OPT)
    model: str = "oracle_ar1"        # forecast model (repro.forecast.models)
    rho: float = fmodels.AR1_RHO
    sa: SAConfig = SAConfig(pop=32, iters=40, sweeps=1)       # per replan
    sa_phase1: SAConfig = SAConfig(pop=48, iters=80)          # OPT makespan


class MPCResult(NamedTuple):
    """Leading axes from :func:`solve_mpc_batch`: [B instances, S seeds]."""

    start: jnp.ndarray            # int32 [T] final executed plan
    assign: jnp.ndarray           # int32 [T]
    opt_makespan: jnp.ndarray     # phase-1 OPT (epochs)
    deadline: jnp.ndarray         # floor(stretch * OPT)
    baseline: common.ScheduleResult   # carbon-agnostic plan, true-trace eval
    realized: common.ScheduleResult   # final plan evaluated on the true trace
    plans_start: jnp.ndarray      # int32 [K, T] incumbent after each replan
    plans_assign: jnp.ndarray     # int32 [K, T]
    frozen_counts: jnp.ndarray    # int32 [K] tasks frozen at each boundary
    planned_carbon: jnp.ndarray   # float32 [K] plan's carbon under its forecast


def forecast_cum(point: jnp.ndarray) -> jnp.ndarray:
    """Cumulative carbon-energy of a (forecast) intensity; float32 [E+1]."""
    return jnp.concatenate([
        jnp.zeros((1,), jnp.float32),
        jnp.cumsum(point.astype(jnp.float32) * EPOCH_HOURS)])


def _project(prio, assign, frozen, start_inc, assign_inc):
    """Clamp a candidate onto the frozen prefix (see module docstring)."""
    prio = jnp.minimum(prio, FREE_CEIL)
    prio = jnp.where(frozen, FROZEN_BAND - start_inc.astype(jnp.float32),
                     prio)
    assign = jnp.where(frozen, assign_inc, assign).astype(jnp.int32)
    return prio, assign


def _frozen_instance(inst: PackedInstance, frozen, start, assign,
                     r) -> PackedInstance:
    """Pin frozen tasks at (start, machine); bar free tasks from the past."""
    onehot = jnp.arange(inst.M, dtype=jnp.int32)[None, :] == assign[:, None]
    allowed = jnp.where(frozen[:, None], onehot, inst.allowed)
    arrival = jnp.where(frozen, start,
                        jnp.maximum(inst.arrival, r)).astype(jnp.int32)
    return inst._replace(allowed=allowed, arrival=arrival)


@functools.partial(jax.jit, static_argnames=("objective", "cfg"))
def solve_mpc(inst: PackedInstance, truth: jnp.ndarray, cum_true: jnp.ndarray,
              key: jax.Array, fc_key: jax.Array, scale: jnp.ndarray,
              objective: str = "carbon",
              cfg: MPCConfig = MPCConfig()) -> MPCResult:
    """Rolling-horizon replanning of one instance (see module docstring).

    ``truth``: realized intensity [E] — the forecasts' ground truth.
    ``cum_true``: cumulative carbon-energy [E+1] used for *realized*
    evaluation (pass the trace's own ``cumulative()`` so every method in a
    benchmark is scored by the same integral).  ``fc_key`` seeds the
    forecast error draws (folded per replan); ``key`` seeds the search.
    ``cfg.n_replans`` should cover the deadline (``n_replans * every >=
    stretch * OPT``); later boundaries freeze everything and degenerate to
    no-ops.
    """
    sweeps = max(cfg.sa.sweeps, 1)
    k1, k_run = jax.random.split(key)

    # ---- Phase 1: carbon-agnostic OPT fixes the deadline and the initial
    # incumbent (the plan a day-ahead deployment would start executing).
    p1 = solve_sa(inst, cum_true, NO_DEADLINE, k1, objective="makespan",
                  machine_rule="earliest_finish", cfg=cfg.sa_phase1)
    baseline = common.decode_full(
        inst, cum_true, NO_DEADLINE, p1.prio, p1.assign,
        objective="makespan", machine_rule="earliest_finish", sweeps=0)
    opt_ms = baseline.makespan
    deadline = jnp.floor(cfg.stretch * opt_ms.astype(jnp.float32) + 1e-6
                         ).astype(jnp.int32)

    def replan(carry, k):
        start, assign, key = carry
        r = (k * cfg.every).astype(jnp.int32)
        frozen = inst.task_mask & (start < r)
        inst_k = _frozen_instance(inst, frozen, start, assign, r)

        fc = fmodels.issue(truth, r, key=jax.random.fold_in(fc_key, k),
                           model=cfg.model, scale=scale, rho=cfg.rho)
        cum_k = forecast_cum(fc.point)

        prio0, assign0 = _project(-start.astype(jnp.float32), assign,
                                  frozen, start, assign)
        key, k_sa = jax.random.split(key)
        out = solve_sa(inst_k, cum_k, deadline, k_sa, objective=objective,
                       machine_rule="fixed", cfg=cfg.sa,
                       prio_init=prio0, assign_init=assign0, frozen=frozen)
        prio_f, assign_f = _project(out.prio, out.assign, frozen, start,
                                    assign)
        cand = common.decode_full(inst_k, cum_k, deadline, prio_f, assign_f,
                                  objective=objective, machine_rule="fixed",
                                  sweeps=sweeps, frozen=frozen)
        inc = common.decode_full(inst_k, cum_k, deadline, prio0, assign0,
                                 objective=objective, machine_rule="fixed",
                                 sweeps=sweeps, frozen=frozen)
        # Keep whichever plan the *current* forecast scores better (the
        # incumbent decode is feasible by construction, so this is the same
        # warm-start guard bilevel uses).
        better = (common.fitness_of(inst_k, cand, deadline, objective)
                  < common.fitness_of(inst_k, inc, deadline, objective))
        pick = lambda a, b: jnp.where(better, a, b)
        new_start = pick(cand.start, inc.start)
        new_assign = pick(cand.assign, inc.assign)
        planned = pick(cand.carbon, inc.carbon)
        return ((new_start, new_assign, key),
                (new_start, new_assign, frozen.sum().astype(jnp.int32),
                 planned))

    init = (baseline.start, baseline.assign, k_run)
    (start, assign, _), (plans_s, plans_a, frozen_counts, planned) = \
        jax.lax.scan(replan, init,
                     jnp.arange(cfg.n_replans, dtype=jnp.int32))

    obj = evaluate(inst, start, assign, cum_true)
    realized = common.ScheduleResult(
        start, assign, obj.makespan, obj.energy, obj.carbon,
        utilization(inst, start, assign))

    return MPCResult(
        start=start, assign=assign, opt_makespan=opt_ms, deadline=deadline,
        baseline=baseline, realized=realized,
        plans_start=plans_s, plans_assign=plans_a,
        frozen_counts=frozen_counts, planned_carbon=planned)


def solve_mpc_batch(insts: PackedInstance, truths: jnp.ndarray,
                    cums_true: jnp.ndarray, keys: jax.Array,
                    fc_keys: jax.Array, scale, **kw) -> MPCResult:
    """vmap of :func:`solve_mpc` over [B] instances x [S] forecast seeds.

    ``insts``/``truths``/``cums_true``/``keys``: leading [B]; ``fc_keys``:
    [S].  ``scale`` is shared.  Result axes: [B, S, ...].
    """
    scale = jnp.float32(scale)
    per_seed = jax.vmap(
        lambda inst, truth, cum, key, fck: functools.partial(
            solve_mpc, **kw)(inst, truth, cum, key, fck, scale),
        in_axes=(None, None, None, None, 0))
    return jax.vmap(per_seed, in_axes=(0, 0, 0, 0, None))(
        insts, truths, cums_true, keys, fc_keys)
