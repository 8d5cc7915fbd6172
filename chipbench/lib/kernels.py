"""Operations and bytes a kernel's algorithm needs, from its shapes.

What an implementation adds (padding, re-reads, a walk over the horizon)
does not count, so a kernel's work reads the same whatever computes it.
"""
from __future__ import annotations


def schedule_eval(batch: int, pop: int, tasks: int, horizon: int) -> dict:
    """One population-carbon call over ``batch`` instances: per instance
    ``pop x tasks`` start and duration words in and delta words out, and
    one ``horizon + 1`` cumulative trace in; one subtraction per slot."""
    slots = batch * pop * tasks
    return {"bytes": slots * 3 * 4 + batch * (horizon + 1) * 4,
            "flops": slots}
