"""Shared pieces for the population solvers: candidate decoding + fitness.

A candidate is ``(prio[T] float32, assign[T] int32)``.  Decoding = SGS
(+ carbon timing sweep for the carbon/energy objectives); fitness = the
objective plus a penalty proportional to the shared validator's violation
mass (:func:`repro.core.validate.total_violations`, Eqs. 4-8 + budget), so
the constrained problem (makespan <= S * OPT) is handled by the same
unconstrained search.  SGS output is feasible for Eqs. 4-8 by construction,
so for plain solves only the budget term can fire — but routing the penalty
through the validator means *any* constraint a decode path might miss (e.g.
a frozen-prefix instance transform) is priced by the same source of truth
the tests check.

Padded instances (mixed-shape scenario batches from
``repro.scenarios.batching``) decode unchanged: padded tasks schedule
instantly at zero duration, padded machines are never ``allowed`` so
neither SGS machine rules nor :func:`random_allowed_assign` can pick them,
and both the objectives and the validator mask padding out.

The paper's energy objective uses carbon as a tiny tie-break weight
(Section 3.2, "Optimizing for energy usage vs carbon emissions") — we use
1e-6 gCO2/kWh-scale weight, below the smallest energy quantum (one epoch of
the smallest server = 0.0625 kWh).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.decoder import sgs, sweep_table, timing_sweep
from repro.core.instance import PackedInstance
from repro.core.objectives import Objectives, energy, evaluate, utilization
from repro.core.validate import total_violations
from repro.kernels import ops
from repro.obs.scopes import scope

OBJECTIVES = ("makespan", "carbon", "energy")
VIOLATION_PENALTY = 1e5      # fitness units per unit of validator mass
ENERGY_CARBON_TIEBREAK = 1e-6


class ScheduleResult(NamedTuple):
    start: jnp.ndarray
    assign: jnp.ndarray
    makespan: jnp.ndarray
    energy: jnp.ndarray
    carbon: jnp.ndarray
    utilization: jnp.ndarray


def _decode(inst: PackedInstance, cum: jnp.ndarray, deadline: jnp.ndarray,
            prio: jnp.ndarray, assign: jnp.ndarray, objective: str,
            machine_rule: str, sweeps: int, frozen: jnp.ndarray | None,
            table: jnp.ndarray | None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Candidate -> ``(start, assign)``: SGS, then the timing sweep unless
    the objective is makespan or ``sweeps`` is 0."""
    dec = sgs(inst, prio, assign, machine_rule=machine_rule)
    start = dec.start
    if objective != "makespan" and sweeps > 0:
        start = timing_sweep(inst, start, dec.assign, cum, deadline, sweeps,
                             frozen=frozen, table=table)
    return start, dec.assign


def _combine(objective: str, carbon: jnp.ndarray, energy: jnp.ndarray,
             pen: jnp.ndarray) -> jnp.ndarray:
    """The fitness of a carbon or energy objective: its value (energy with
    carbon as the paper's tie-break) plus the infeasibility penalty."""
    if objective == "carbon":
        return carbon + pen
    if objective == "energy":
        return energy + ENERGY_CARBON_TIEBREAK * carbon + pen
    raise ValueError(f"unknown objective {objective!r}")


@functools.partial(jax.jit,
                   static_argnames=("objective", "machine_rule", "sweeps"))
def decode_full(inst: PackedInstance, cum: jnp.ndarray, deadline: jnp.ndarray,
                prio: jnp.ndarray, assign: jnp.ndarray,
                objective: str = "carbon", machine_rule: str = "fixed",
                sweeps: int = 2,
                frozen: jnp.ndarray | None = None,
                table: jnp.ndarray | None = None) -> ScheduleResult:
    """Candidate -> feasible schedule + objective values.

    ``frozen`` (optional bool [T]) marks already-executing tasks the timing
    sweep must not move (rolling replans); SGS placement of frozen tasks is
    pinned upstream via the instance transform + priority band (see
    :mod:`repro.core.solvers.rolling`).  ``table`` is the instance's
    :func:`repro.core.decoder.sweep_table`, built here when not given.
    """
    start, assign = _decode(inst, cum, deadline, prio, assign, objective,
                            machine_rule, sweeps, frozen, table)
    with scope("objectives"):
        obj: Objectives = evaluate(inst, start, assign, cum)
        return ScheduleResult(start, assign, obj.makespan, obj.energy,
                              obj.carbon, utilization(inst, start, assign))


def fitness_of(inst: PackedInstance, res: ScheduleResult,
               deadline: jnp.ndarray, objective: str) -> jnp.ndarray:
    """Objective value + validator-priced infeasibility penalty.

    The penalty term is the shared validator's scalar violation mass
    (arrival/precedence/overlap epochs, weighted disallowed assignments,
    epochs past ``deadline``) — zero iff the schedule is feasible, so the
    unconstrained search and the feasibility tests agree on what counts.
    """
    with scope("objectives"):
        if objective == "makespan":
            return res.makespan.astype(jnp.float32)
        pen = VIOLATION_PENALTY * total_violations(
            inst, res.start, res.assign, deadline).astype(jnp.float32)
        return _combine(objective, res.carbon, res.energy, pen)


@functools.partial(jax.jit,
                   static_argnames=("objective", "machine_rule", "sweeps"))
def fitness_fn(inst: PackedInstance, cum: jnp.ndarray, deadline: jnp.ndarray,
               prio: jnp.ndarray, assign: jnp.ndarray, objective: str,
               machine_rule: str, sweeps: int,
               frozen: jnp.ndarray | None = None,
               table: jnp.ndarray | None = None) -> jnp.ndarray:
    res = decode_full(inst, cum, deadline, prio, assign,
                      objective=objective, machine_rule=machine_rule,
                      sweeps=sweeps, frozen=frozen, table=table)
    return fitness_of(inst, res, deadline, objective)


def population_fitness(inst: PackedInstance, cum: jnp.ndarray,
                       deadline: jnp.ndarray, prio: jnp.ndarray,
                       assign: jnp.ndarray, objective: str,
                       machine_rule: str, sweeps: int,
                       frozen: jnp.ndarray | None = None,
                       use_kernels: bool | None = None,
                       table: jnp.ndarray | None = None) -> jnp.ndarray:
    """Fitness of a whole candidate population.  prio/assign [Pop, T] -> [Pop].

    The SA hot loop: every proposal evaluation, init evaluation and
    migration re-evaluation goes through here.  One fork, chosen by
    :func:`repro.kernels.ops.kernels_enabled` (``use_kernels`` first, then
    ``REPRO_KERNELS``, then the platform: on a TPU by default):

    * jnp path — ``vmap(fitness_fn)``, the golden-locked reference;
    * kernel path — the same decode (:func:`_decode`) and the same
      combination of objectives (:func:`_combine`), but the carbon trace
      integral runs once for the whole population in the Pallas kernel
      (:func:`repro.kernels.ops.population_carbon`) instead of Pop
      separate gather chains.

    The two are meant to be bit-exact equal (``tests/test_kernels.py``
    property-tests it); on jax 0.9 the carbon can differ by one ulp.  The
    makespan objective never touches the trace, so it always takes the
    jnp path.  Both paths sweep with one ``table``
    (:func:`repro.core.decoder.sweep_table`), which solvers build once
    per solve.  Meant to be called from inside the solvers' jitted
    scope with ``use_kernels`` static (the branch resolves at trace time;
    NB flipping ``REPRO_KERNELS`` after a solver cached its trace has no
    effect on that cache — pass the argument in tests).
    """
    if objective != "makespan" and sweeps > 0 and table is None:
        table = sweep_table(inst, cum)
    if objective != "makespan" and ops.kernels_enabled(use_kernels):
        starts, assigns = jax.vmap(lambda p, a: _decode(
            inst, cum, deadline, p, a, objective, machine_rule, sweeps,
            frozen, table))(prio, assign)
        with scope("objectives"):
            carb = ops.population_carbon(inst, starts, assigns, cum)
            pen = VIOLATION_PENALTY * jax.vmap(
                lambda s, a: total_violations(inst, s, a, deadline)
            )(starts, assigns).astype(jnp.float32)
            en = jax.vmap(lambda a: energy(inst, a))(assigns)
            return _combine(objective, carb, en, pen)
    return jax.vmap(lambda p, a: fitness_fn(
        inst, cum, deadline, p, a, objective, machine_rule, sweeps,
        frozen=frozen, table=table))(prio, assign)


def random_allowed_assign(key: jax.Array, inst: PackedInstance,
                          shape: tuple[int, ...] = ()) -> jnp.ndarray:
    """Uniform random machine among each task's allowed set."""
    g = jax.random.gumbel(key, shape + (inst.T, inst.M))
    return jnp.argmax(jnp.where(inst.allowed, g, -jnp.inf), axis=-1).astype(jnp.int32)
