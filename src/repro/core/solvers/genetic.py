"""Genetic-algorithm solver over SGS encodings (ablation partner to SA).

Continuous priority vectors make crossover trivial (uniform gene mix keeps
any blend decodable — SGS repairs everything into a feasible schedule), so
no precedence-repair operator is needed.  Tournament selection + elitism.
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
from repro.core.solvers.annealing import SolveOut


class GAConfig(NamedTuple):
    pop: int = 128
    gens: int = 120
    sweeps: int = 2
    sigma: float = 3.0
    tourn: int = 4           # tournament size
    p_cross: float = 0.7
    p_mut_prio: float = 0.25
    p_mut_mach: float = 0.25
    elite: int = 4


@functools.partial(jax.jit,
                   static_argnames=("objective", "machine_rule", "cfg",
                                    "use_kernels"))
def solve_ga(inst: PackedInstance, cum: jnp.ndarray, deadline: jnp.ndarray,
             key: jax.Array, objective: str = "carbon",
             machine_rule: str = "fixed", cfg: GAConfig = GAConfig(),
             prio_init: jnp.ndarray | None = None,
             assign_init: jnp.ndarray | None = None,
             frozen: jnp.ndarray | None = None,
             use_kernels: bool | None = None) -> SolveOut:
    """``use_kernels`` selects the Pallas fitness path (bit-exact equal to
    the jnp path); ``None`` defers to ``REPRO_KERNELS`` / the backend
    default — see :func:`repro.core.solvers.common.population_fitness`."""
    with scope("search"):
        T = inst.T
        # Frozen tasks (rolling replans) keep their exact priorities: init
        # noise and mutations are masked, and crossover mixes identical
        # frozen genes.
        free = (jnp.ones((T,), bool) if frozen is None else ~frozen)
        sweeps = 0 if objective == "makespan" else cfg.sweeps
        table = sweep_table(inst, cum) if sweeps else None
        fit_v = lambda p, a: common.population_fitness(  # noqa: E731
            inst, cum, deadline, p, a, objective, machine_rule, sweeps,
            frozen=frozen, use_kernels=use_kernels, table=table)

        k_init, k_assign, k_run = jax.random.split(key, 3)
        base = upward_rank(inst) if prio_init is None else prio_init
        prio = base[None, :] + cfg.sigma * jax.random.normal(
            k_init, (cfg.pop, T)) * free
        prio = prio.at[0].set(base)
        if assign_init is None:
            assign = common.random_allowed_assign(k_assign, inst, (cfg.pop,))
        else:
            assign = jnp.broadcast_to(assign_init, (cfg.pop, T)
                                      ).astype(jnp.int32)
        fit = fit_v(prio, assign)

        def gen(carry, _):
            key, prio, assign, fit = carry
            key, k1, k2, k3, k4, k5, k6, k7 = jax.random.split(key, 8)

            # Tournament selection of two parent pools.
            idx = jax.random.randint(k1, (2, cfg.pop, cfg.tourn), 0, cfg.pop)
            tf = fit[idx]                                # [2, pop, tourn]
            winners = jnp.take_along_axis(
                idx, jnp.argmin(tf, axis=-1)[..., None],
                -1)[..., 0]                                  # [2, pop]
            pa, pb = winners

            # Uniform crossover on priorities and machines.
            do_c = jax.random.bernoulli(k2, cfg.p_cross, (cfg.pop, 1))
            gene = jax.random.bernoulli(k3, 0.5, (cfg.pop, T))
            child_p = jnp.where(gene & do_c, prio[pb], prio[pa])
            child_a = jnp.where(gene & do_c, assign[pb], assign[pa])

            # Mutation.
            mut_p = jax.random.bernoulli(k4, cfg.p_mut_prio, (cfg.pop, 1)) & \
                jax.random.bernoulli(k5, 2.0 / T, (cfg.pop, T)) & free
            child_p = child_p + mut_p * cfg.sigma * jax.random.normal(
                k5, (cfg.pop, T))
            mut_m = jax.random.bernoulli(k6, cfg.p_mut_mach, (cfg.pop, 1)) & \
                (jax.random.randint(k7, (cfg.pop, 1), 0, T)
                 == jnp.arange(T)[None, :])
            rnd_m = common.random_allowed_assign(k7, inst, (cfg.pop,))
            child_a = jnp.where(mut_m, rnd_m, child_a)

            child_f = fit_v(child_p, child_a)

            # Elitism: keep the cfg.elite best of the old population.
            order = jnp.argsort(fit)
            elite_slots = jnp.arange(cfg.pop) < cfg.elite
            new_p = jnp.where(elite_slots[:, None], prio[order], child_p)
            new_a = jnp.where(elite_slots[:, None], assign[order], child_a)
            new_f = jnp.where(elite_slots, fit[order], child_f)
            return (key, new_p, new_a, new_f), None

        (_, prio, assign, fit), _ = jax.lax.scan(
            gen, (k_run, prio, assign, fit), None, length=cfg.gens)
        i = jnp.argmin(fit)
        return SolveOut(prio[i], assign[i], fit[i])
