"""Massively-parallel simulated annealing over SGS encodings.

``pop`` independent Metropolis chains run in lockstep under ``vmap``; every
``migrate_every`` iterations the worst quartile of chains is re-seeded from
the global best (a cheap exploitation step that mimics CP-SAT's solution
sharing between workers).  The whole solve is a single ``lax.scan`` — one
XLA program, no host round-trips — and vmaps again over batched instances.

This is the TPU-native replacement for the paper's CP-SAT search
(DESIGN.md §3): thousands of dumb concurrent searches instead of one clever
sequential one.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.decoder import sweep_table, upward_rank
from repro.core.instance import PackedInstance
from repro.core.solvers import common
from repro.obs.scopes import scope


class SAConfig(NamedTuple):
    pop: int = 128
    iters: int = 200
    sweeps: int = 2            # carbon timing sweeps inside the decode
    sigma: float = 3.0         # priority-noise scale (epochs of rank)
    p_machine_move: float = 0.35
    migrate_every: int = 25
    t0_frac: float = 0.3       # initial temperature = frac * fitness IQR
    t_decay: float = 0.97


class SolveOut(NamedTuple):
    prio: jnp.ndarray     # best candidate found
    assign: jnp.ndarray
    fitness: jnp.ndarray  # its fitness


@functools.partial(jax.jit,
                   static_argnames=("objective", "machine_rule", "cfg",
                                    "use_kernels"))
def solve_sa(inst: PackedInstance, cum: jnp.ndarray, deadline: jnp.ndarray,
             key: jax.Array, objective: str = "carbon",
             machine_rule: str = "fixed", cfg: SAConfig = SAConfig(),
             prio_init: jnp.ndarray | None = None,
             assign_init: jnp.ndarray | None = None,
             frozen: jnp.ndarray | None = None,
             use_kernels: bool | None = None) -> SolveOut:
    """Minimize ``objective`` (see solvers.common) over SGS candidates.

    ``frozen`` (optional bool [T]) marks already-executing tasks (rolling
    replans): their priorities are never perturbed — init noise, proposals
    and migration all mask them — so the executed prefix the caller encoded
    in ``prio_init``/``assign_init`` survives the whole search exactly, and
    the timing sweep inside the decode never moves them either.

    ``use_kernels`` selects the Pallas fitness path (bit-exact equal to
    the jnp path — the solve result is identical either way); ``None``
    defers to ``REPRO_KERNELS`` / the backend default, see
    :func:`repro.core.solvers.common.population_fitness`.
    """
    with scope("search"):
        T = inst.T
        free = (jnp.ones((T,), bool) if frozen is None else ~frozen)
        sweeps = 0 if objective == "makespan" else cfg.sweeps
        table = sweep_table(inst, cum) if sweeps else None
        fit_v = lambda p, a: common.population_fitness(  # noqa: E731
            inst, cum, deadline, p, a, objective, machine_rule, sweeps,
            frozen=frozen, use_kernels=use_kernels, table=table)

        k_init, k_assign, k_run = jax.random.split(key, 3)
        rank = upward_rank(inst)
        if prio_init is None:
            prio_init = rank
        prio = (prio_init[None, :]
                + cfg.sigma * jax.random.normal(k_init, (cfg.pop, T)) * free)
        # Keep one undisturbed copy of the init (chain 0).
        prio = prio.at[0].set(prio_init)
        if assign_init is None:
            assign = common.random_allowed_assign(k_assign, inst, (cfg.pop,))
        else:
            assign = jnp.broadcast_to(assign_init, (cfg.pop, T)
                                      ).astype(jnp.int32)
        fit = fit_v(prio, assign)

        spread = jnp.percentile(fit, 75) - jnp.percentile(fit, 25)
        t0 = cfg.t0_frac * jnp.maximum(spread, 1e-3)

        b0 = jnp.argmin(fit)
        best = (prio[b0], assign[b0], fit[b0])

        def step(carry, it):
            key, prio, assign, fit, best = carry
            key, k1, k2, k3, k4, k5, k6 = jax.random.split(key, 7)
            temp = t0 * cfg.t_decay ** it

            # Priority proposal: gaussian noise on a random ~2-task subset.
            mask = jax.random.bernoulli(k1, 2.0 / T, (cfg.pop, T)) & free
            dp = cfg.sigma * jax.random.normal(k2, (cfg.pop, T)) * mask
            new_prio = prio + dp
            # Machine proposal: with prob p, reassign one random task.
            do_m = jax.random.bernoulli(k3, cfg.p_machine_move, (cfg.pop,))
            t_idx = jax.random.randint(k4, (cfg.pop,), 0, T)
            new_m = common.random_allowed_assign(k5, inst, (cfg.pop,))
            picked = jnp.take_along_axis(new_m, t_idx[:, None], 1)[:, 0]
            new_assign = jnp.where(
                (jnp.arange(T)[None, :] == t_idx[:, None]) & do_m[:, None],
                picked[:, None], assign)

            new_fit = fit_v(new_prio, new_assign)
            u = jax.random.uniform(k6, (cfg.pop,))
            accept = (new_fit < fit) | (u < jnp.exp(-(new_fit - fit)
                                                    / jnp.maximum(temp, 1e-6)))
            prio = jnp.where(accept[:, None], new_prio, prio)
            assign = jnp.where(accept[:, None], new_assign, assign)
            fit = jnp.where(accept, new_fit, fit)

            # Track global best.
            i = jnp.argmin(fit)
            bp, ba, bf = best
            better = fit[i] < bf
            best = (jnp.where(better, prio[i], bp),
                    jnp.where(better, assign[i], ba),
                    jnp.where(better, fit[i], bf))

            # Migration: worst quartile <- best + fresh noise.
            def migrate(args):
                key, prio, assign, fit = args
                kk1, kk2 = jax.random.split(key)
                thresh = jnp.percentile(fit, 75)
                worst = fit >= thresh
                mp = best[0][None, :] + cfg.sigma * jax.random.normal(
                    kk1, (cfg.pop, T)) * free
                prio = jnp.where(worst[:, None], mp, prio)
                assign = jnp.where(worst[:, None],
                                   jnp.broadcast_to(best[1], (cfg.pop, T)),
                                   assign)
                fit = jnp.where(worst, fit_v(prio, assign), fit)
                return prio, assign, fit

            key, km = jax.random.split(key)
            prio, assign, fit = jax.lax.cond(
                (it % cfg.migrate_every) == cfg.migrate_every - 1,
                migrate, lambda a: (a[1], a[2], a[3]), (km, prio, assign, fit))
            return (key, prio, assign, fit, best), None

        (_, _, _, _, best), _ = jax.lax.scan(
            step, (k_run, prio, assign, fit, best),
            jnp.arange(cfg.iters, dtype=jnp.int32))
        return SolveOut(*best)
