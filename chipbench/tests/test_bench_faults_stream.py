"""A stream run with the timed path broken underneath reads not correct.

Each fault is planted in the scheduler's pool tick (``_pool_tick``) or
admission solve (``_admission_eval``), which the engine looks up by name
on every call; the rest of the run is the benchmark's own, at a size the
CPU holds.
"""
import jax
import jax.numpy as jnp
import pytest

from benchcase import run_small

CELL = "stream.poisson_0.9"


def test_sound_run_is_correct(monkeypatch):
    res = run_small(CELL, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "stream_jobs_per_s",
                                   "stream_admit_p95_ms"}


def _unchanged(real):
    def tick(pool, cp, lstate, mfree, dirty, budget, t, machine_rule):
        done = jnp.all(lstate.scheduled | ~pool.task_mask, axis=1)
        comp = jnp.max(jnp.where(pool.task_mask, lstate.comp, 0), axis=1)
        return lstate, mfree, done, comp
    return tick


def _half(real):
    def tick(pool, cp, lstate, mfree, dirty, budget, t, machine_rule):
        new, mf, done, comp = real(pool, cp, lstate, mfree, dirty, budget, t,
                                   machine_rule=machine_rule)
        half = pool.dur.shape[0] // 2
        keep = lambda a, b: a.at[half:].set(b[half:])  # noqa: E731
        new = jax.tree.map(keep, new, lstate)
        done = jnp.all(new.scheduled | ~pool.task_mask, axis=1)
        comp = jnp.max(jnp.where(pool.task_mask, new.comp, 0), axis=1)
        return new, keep(mf, mfree), done, comp
    return tick


def _altered(real):
    """The admission decision altered where it is made: every job's
    stretch budget one epoch looser."""
    def admit(*args, **kw):
        cp, budget, obj, complete = real(*args, **kw)
        return cp, budget + 1, obj, complete
    return admit


@pytest.mark.parametrize("target,fault", [
    ("_pool_tick", _unchanged), ("_pool_tick", _half),
    ("_admission_eval", _altered)],
    ids=["state_unchanged", "half_the_lanes", "answer_altered"])
def test_fault_reads_not_correct(monkeypatch, target, fault):
    import repro.stream.engine as engine
    monkeypatch.setattr(engine, target, fault(getattr(engine, target)))
    res = run_small(CELL, monkeypatch)
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["checks"]["jobs_mismatched"]["value"] > 0
