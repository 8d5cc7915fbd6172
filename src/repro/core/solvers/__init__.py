from repro.core.solvers.common import ScheduleResult, fitness_fn, decode_full
from repro.core.solvers.annealing import solve_sa
from repro.core.solvers.bilevel import BilevelResult, solve_bilevel, solve_bilevel_batch
from repro.core.solvers.online import online_carbon_gated, online_greedy
from repro.core.solvers.online_jax import (OnlineSchedule, SweepResult,
                                           online_carbon_gated_jax,
                                           online_greedy_jax, policy_grid,
                                           simulate_online, sweep_policies)
from repro.core.solvers.rolling import (MPCConfig, MPCResult, solve_mpc,
                                        solve_mpc_batch)

__all__ = [
    "ScheduleResult", "fitness_fn", "decode_full", "solve_sa",
    "BilevelResult", "solve_bilevel", "solve_bilevel_batch",
    "online_carbon_gated", "online_greedy",
    "OnlineSchedule", "SweepResult", "online_carbon_gated_jax",
    "online_greedy_jax", "policy_grid", "simulate_online", "sweep_policies",
    "MPCConfig", "MPCResult", "solve_mpc", "solve_mpc_batch",
]
