"""The measured window: whole units of work back to back, and the
arithmetic on them.

A unit is one whole call into the system under test that ends with its
answer on the host: a solved batch of instances, or a served stream.  The
window runs units until ``seconds`` have passed since the first one
started; the last unit runs to its end, so every unit in the window is
whole.  A rate is all the work of those units over the wall time from the
first unit's start to the last unit's end.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable

import numpy as np


@dataclasses.dataclass
class Unit:
    index: int          # which prepared input the unit ran
    start: float        # host clock, seconds
    end: float
    work: int           # instances solved or jobs served
    out: Any            # the answer, on the host
    traced: bool = False


def run(step: Callable[[int], tuple[int, Any]], n_inputs: int,
        seconds: float, *, min_units: int = 1,
        traced: Callable[[], Any] | None = None,
        clock: Callable[[], float] = time.perf_counter) -> list[Unit]:
    """Run ``step(i)`` (returning ``(work, answer)``) for ``i = 0, 1, ...``
    over the prepared inputs ``i mod n_inputs`` until ``seconds`` have
    passed and at least ``min_units`` units ran.  ``traced`` (a context
    manager factory) wraps the first unit only."""
    units: list[Unit] = []
    t0 = None
    while True:
        i = len(units)
        ctx = traced() if (traced is not None and i == 0) \
            else contextlib.nullcontext()
        with ctx:
            s = clock()
            work, out = step(i % n_inputs)
            e = clock()
        if t0 is None:
            t0 = s
        units.append(Unit(i % n_inputs, s, e, work, out,
                          traced=traced is not None and i == 0))
        if e - t0 >= seconds and len(units) >= min_units:
            return units


def rate(units: list[Unit]) -> float:
    """All the work of the units over the time from the first start to the
    last end."""
    return sum(u.work for u in units) / (units[-1].end - units[0].start)


def percentile(samples, q: float) -> float:
    """The ``q``-th percentile of all samples (linear interpolation)."""
    return float(np.percentile(np.asarray(samples, np.float64), q))
