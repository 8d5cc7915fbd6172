"""Stable names for the offline bound's stages on the device.

Each stage of :func:`repro.core.solvers.solve_bilevel` runs under a
``jax.named_scope`` of one of these names.  The scope lands in every HLO
instruction's ``op_name`` metadata, so a profile of the compiled bound can
be split by stage (``docs/observability.md``, "Stages on the device").  A
scope is metadata only: it changes neither what XLA fuses nor what the
program computes, and costs nothing at run time.

=================  =======================================================
``sgs``            the serial placement scan (:func:`repro.core.decoder.sgs`)
``timing_sweep``   the carbon timing sweeps, row selection included
``sweep_table``    the per-instance start-cost table (TPU only)
``objectives``     a decoded schedule's objectives and violation penalty,
                   the ``schedule_eval`` kernel included
``search``         the SA search around them: init, RNG, proposals,
                   acceptance, best tracking, migration
``phase1``         the makespan phase and its decode
``phase2``         the carbon (or energy) phase, its table, final decodes
                   and fallback
=================  =======================================================
"""
from __future__ import annotations

import jax

STAGES = ("sgs", "timing_sweep", "sweep_table", "objectives", "search")
PHASES = ("phase1", "phase2")


def scope(name: str):
    """``jax.named_scope(name)`` for a stage or phase; any other name
    raises ``ValueError``."""
    if name not in STAGES + PHASES:
        raise ValueError(f"{name!r} is not a stage {STAGES} or phase "
                         f"{PHASES}")
    return jax.named_scope(name)
