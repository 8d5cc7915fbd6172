"""A bound run with the timed path broken underneath reads not correct.

Each fault is planted in the scheduler (the batch entry point that
``drivers/bound.py`` imports at set-up, or the SA solver the bound calls);
the rest of the run is the benchmark's own, at a size the CPU holds.
"""
import jax
import jax.numpy as jnp
import pytest

from benchcase import run_small

CELL = "bound.paper_s1"


def test_sound_run_is_correct(monkeypatch):
    res = run_small(CELL, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "bound_instances_per_s"}


def _unchanged(monkeypatch):
    """The search's step leaves its state as it was: no SA iteration."""
    import repro.core.solvers.bilevel as bilevel
    real = bilevel.solve_sa

    def solve_sa(*args, cfg, **kw):
        return real(*args, cfg=cfg._replace(iters=0), **kw)
    monkeypatch.setattr(bilevel, "solve_sa", solve_sa)


def _half(monkeypatch):
    """Half of the batch solved; its answers stand in for the rest."""
    import repro.core.solvers as solvers
    real = solvers.solve_bilevel_batch

    def batch(insts, cums, keys, **kw):
        n = cums.shape[0] // 2
        res = real(jax.tree.map(lambda x: x[:n], insts), cums[:n], keys[:n],
                   **kw)
        return jax.tree.map(lambda x: jnp.concatenate([x, x]), res)
    monkeypatch.setattr(solvers, "solve_bilevel_batch", batch)


def _altered(monkeypatch):
    """One answer altered where it is made: the first task of every
    optimized schedule starts an epoch later."""
    import repro.core.solvers as solvers
    real = solvers.solve_bilevel_batch

    def batch(*args, **kw):
        res = real(*args, **kw)
        opt = res.optimized
        return res._replace(optimized=opt._replace(
            start=opt.start.at[:, 0].add(1)))
    monkeypatch.setattr(solvers, "solve_bilevel_batch", batch)


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered],
                         ids=["state_unchanged", "half_the_batch",
                              "answer_altered"])
def test_fault_reads_not_correct(monkeypatch, fault):
    jax.clear_caches()
    fault(monkeypatch)
    res = run_small(CELL, monkeypatch)
    jax.clear_caches()
    assert not res["correct"]
    assert res["failed"] > 0 or res["checks"]["unimproved_share"]["value"] \
        > res["checks"]["unimproved_share"]["limit"]
