"""Core FJSP layer: instances, objectives, decoders, solvers.

Property tests (hypothesis) pin the feasibility invariants of the SGS
decoder and timing sweep; the exact oracle certifies optimality on tiny
instances (replacing the paper's CP-SAT ground truth).
"""
import dataclasses
import functools
from unittest import mock

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from repro.core import (generate_instance, pack, stack_packed, synthesize,
                        validate)
from repro.core.carbon import constant, sample_window
from repro.core.decoder import (_select_row, sgs, sweep_table, timing_sweep,
                                upward_rank)
from repro.core.instance import DAG_SHAPES, Job, Instance
from repro.core.objectives import (carbon, energy, evaluate, makespan,
                                   utilization)
from repro.core.validate import check_feasible_np, total_violations as violations
from repro.core.solvers import solve_bilevel, solve_sa
from repro.core.solvers.annealing import SAConfig
from repro.core.solvers.common import decode_full
from repro.core.solvers.exact import exact_carbon, exact_makespan


def _trace_cum(rng, horizon=600, region="AU-SA"):
    tr = synthesize(region, days=10)
    return jnp.asarray(sample_window(tr, rng, horizon).cumulative())


# ---------------------------------------------------------------------------
# Instances + packing.
# ---------------------------------------------------------------------------

def test_pack_shapes_and_padding(rng):
    inst = generate_instance(rng, n_jobs=4, k_tasks=3, n_machines=5,
                             heterogeneous=True)
    p = pack(inst, pad_tasks=20)
    assert p.T == 20 and p.M == 5
    assert int(p.task_mask.sum()) == 12
    assert bool(p.allowed[12:, 0].all())          # padding on machine 0
    # topological indexing: predecessors have smaller index
    pr = np.asarray(p.pred)
    assert not np.triu(pr).any()


def test_hetero_durations_scale(rng):
    inst = generate_instance(rng, n_jobs=2, k_tasks=2, heterogeneous=True)
    d = inst.durations_matrix()
    # slowest machine (speed 1/3) takes ~3x the baseline machine (speed 1)
    assert (d[:, 0] >= d[:, 2]).all() and (d[:, 4] <= d[:, 2]).all()


# ---------------------------------------------------------------------------
# Feasibility properties of the decoders (hypothesis).
# ---------------------------------------------------------------------------

@settings(deadline=None)
@given(seed=st.integers(0, 10_000), k=st.integers(2, 5),
       n=st.integers(2, 5), rule=st.sampled_from(
           ["earliest_finish", "min_energy", "fixed"]))
def test_sgs_always_feasible(seed, k, n, rule):
    rng = np.random.default_rng(seed)
    inst = generate_instance(rng, n_jobs=n, k_tasks=k, n_machines=3,
                             heterogeneous=bool(seed % 2))
    p = pack(inst)
    prio = jnp.asarray(rng.normal(size=p.T), jnp.float32)
    assign = jnp.asarray(rng.integers(0, 3, p.T), jnp.int32)
    dec = sgs(p, prio, assign, machine_rule=rule)
    assert int(violations(p, dec.start, dec.assign)) == 0
    assert not check_feasible_np(p, dec.start, dec.assign)


def _sgs_np(p, prio, assign, rule):
    """Serial SGS in plain numpy, one task per step: the highest-priority
    ready task (lowest index on ties) at its earliest start on the machine
    the rule picks (lowest index on ties)."""
    dur, allowed, pred, arrival, real, power = (np.asarray(a) for a in (
        p.dur, p.allowed, p.pred, p.arrival, p.task_mask, p.power))
    T, M = dur.shape
    done = np.zeros(T, bool)
    comp = np.zeros(T, np.int64)
    mfree = np.zeros(M, np.int64)
    start, aout, seq = (np.zeros(T, np.int64) for _ in range(3))
    for i in range(T):
        ready = [t for t in range(T) if not done[t] and all(
            done[u] for u in range(T) if pred[t, u] and real[u])]
        t = min(ready, key=lambda t: (-prio[t], t))
        base = max([int(arrival[t])] + [int(comp[u]) for u in range(T)
                                        if pred[t, u] and real[u]])
        est = [max(base, int(mfree[m])) for m in range(M)]
        ok = [m for m in range(M) if allowed[t, m]]
        if rule == "fixed":
            m = int(assign[t])
        elif not ok:
            m = 0
        elif rule == "earliest_finish":
            m = min(ok, key=lambda m: (est[m] + dur[t, m], m))
        else:  # min_energy: energy, then finish, packed in one f32 key
            m = min(ok, key=lambda m: (
                np.float32(power[m]) * np.float32(dur[t, m])
                * np.float32(65536) + np.float32(est[m] + dur[t, m]), m))
        done[t] = True
        start[t], comp[t] = est[m], est[m] + dur[t, m]
        mfree[m] = max(mfree[m], comp[t])
        aout[t], seq[t] = m, i
    return start, aout, seq


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 4),
       k=st.integers(1, 5), n_machines=st.integers(1, 5),
       rule=st.sampled_from(["earliest_finish", "min_energy", "fixed"]))
def test_sgs_equals_numpy_reference(seed, n, k, n_machines, rule):
    """Batched SGS places every candidate bit for bit as the plain serial
    SGS does: padded tasks and machines, restricted eligibility,
    heterogeneous fleets, and priority ties drawn on purpose."""
    rng = np.random.default_rng(seed)
    inst = generate_instance(rng, n_jobs=n, k_tasks=k,
                             n_machines=n_machines,
                             heterogeneous=bool(seed % 2))
    elig = tuple(tuple(tuple(sorted(rng.choice(
        n_machines, rng.integers(1, n_machines + 1), replace=False)))
        for _ in job.base_durations) for job in inst.jobs)
    p = pack(dataclasses.replace(inst, allowed=elig),
             pad_tasks=20, pad_machines=6)
    allowed = np.asarray(p.allowed)
    prio = rng.integers(0, 4, (8, p.T)).astype(np.float32)
    assign = np.asarray([[rng.choice(np.flatnonzero(allowed[t]))
                          for t in range(p.T)] for _ in range(8)], np.int32)
    got = jax.vmap(lambda q, a: sgs(p, q, a, machine_rule=rule))(
        jnp.asarray(prio), jnp.asarray(assign))
    for c in range(8):
        want = _sgs_np(p, prio[c], assign[c], rule)
        for g, w in zip(got, want):
            assert np.array_equal(np.asarray(g[c]), w)


@settings(deadline=None)
@given(seed=st.integers(0, 10_000))
def test_timing_sweep_feasible_and_monotone(seed):
    rng = np.random.default_rng(seed)
    inst = generate_instance(rng, n_jobs=3, k_tasks=4, n_machines=3)
    p = pack(inst)
    cum = _trace_cum(rng)
    dec = sgs(p, jnp.asarray(rng.normal(size=p.T), jnp.float32))
    ms0 = makespan(p, dec.start, dec.assign)
    c0 = carbon(p, dec.start, dec.assign, cum)
    deadline = ms0 + 20
    start2 = timing_sweep(p, dec.start, dec.assign, cum,
                          jnp.int32(deadline), sweeps=2)
    assert int(violations(p, start2, dec.assign)) == 0
    assert int(makespan(p, start2, dec.assign)) <= int(deadline)
    assert float(carbon(p, start2, dec.assign, cum)) <= float(c0) + 1e-3


@settings(deadline=None)
@given(seed=st.integers(0, 10_000), slack=st.integers(0, 40))
def test_timing_sweep_docstring_invariants(seed, slack):
    """What the timing_sweep docstring promises: carbon is monotone
    non-increasing as sweeps stack, feasibility (shared validator) is
    preserved, and the deadline is never exceeded."""
    rng = np.random.default_rng(seed)
    inst = generate_instance(rng, n_jobs=3, k_tasks=4, n_machines=3,
                             heterogeneous=bool(seed % 2))
    p = pack(inst)
    cum = _trace_cum(rng)
    dec = sgs(p, jnp.asarray(rng.normal(size=p.T), jnp.float32))
    deadline = jnp.int32(int(makespan(p, dec.start, dec.assign)) + slack)
    prev = float(carbon(p, dec.start, dec.assign, cum))
    for sweeps in (1, 2, 3):
        s = timing_sweep(p, dec.start, dec.assign, cum, deadline,
                         sweeps=sweeps)
        rep = validate.violation_report(p, s, dec.assign, deadline)
        assert all(int(v) == 0 for v in rep)     # feasible incl. deadline
        assert not validate.check_feasible_np(p, s, dec.assign,
                                              int(deadline))
        c = float(carbon(p, s, dec.assign, cum))
        assert c <= prev + 1e-3                  # monotone across sweeps
        prev = c


@functools.partial(jax.jit, static_argnames=("tpu", "sweeps"))
def _rows_and_sweeps(batch, cum, start, assign, deadline, frozen, tpu,
                     sweeps=2):
    """Per instance: every row its table selects (``tpu``; its TPU path,
    built here on the CPU by reporting a TPU backend at trace time) and
    ``sweeps`` sweeps of each candidate, all in one ``jax.jit`` under
    ``vmap`` over instances and candidates, as ``solve_bilevel_batch``
    runs them."""
    def one(inst, c, s, a, dl, fz):
        table = sweep_table(inst, c)
        assert (table is not None) == tpu
        swept = jax.vmap(lambda s1, a1: timing_sweep(
            inst, s1, a1, c, dl, sweeps=sweeps, frozen=fz,
            table=table))(s, a)
        if table is None:
            return None, swept
        return jax.vmap(lambda j: _select_row(table, j))(
            jnp.arange(inst.T * inst.M)), swept
    return jax.vmap(one)(batch, cum, start, assign, deadline, frozen)


def _check_table_equals_gather(rng, insts, cum, slack, frozen=None):
    """The table form selects every start-cost row bit for bit as the
    gather form computes it, so whole sweeps of a population of 8
    candidates agree exactly."""
    batch = stack_packed(insts)
    B, T = len(insts), insts[0].T
    H = cum.shape[1] - 1
    prio = jnp.asarray(rng.normal(size=(B, 8, T)), jnp.float32)
    dec = jax.vmap(jax.vmap(sgs, in_axes=(None, 0)))(batch, prio)
    deadline = jnp.max(jax.vmap(jax.vmap(makespan, in_axes=(None, 0, 0)))(
        batch, dec.start, dec.assign), axis=1) + slack
    args = (batch, cum, dec.start, dec.assign, deadline, frozen)
    assert sweep_table(insts[0], cum[0]) is None
    with mock.patch("jax.default_backend", return_value="tpu"):
        rows, got = _rows_and_sweeps(*args, tpu=True)
    _, ref = _rows_and_sweeps(*args, tpu=False)
    svec = np.arange(H + 1)
    for b, p in enumerate(insts):
        c = np.asarray(cum[b])
        want = c[np.minimum(svec + np.asarray(p.dur).reshape(-1, 1), H)] \
            - c[svec]
        assert np.array_equal(np.asarray(rows[b]), want)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@settings(deadline=None, max_examples=25)
@given(seed=st.integers(0, 10_000), slack=st.integers(0, 60))
def test_timing_sweep_table_equals_gather(seed, slack):
    """The sweep's one-hot table form and its gather form agree bit for
    bit (:func:`_check_table_equals_gather`), with padded and frozen
    tasks too."""
    rng = np.random.default_rng(seed)
    inst = generate_instance(rng, n_jobs=3, k_tasks=4, n_machines=3,
                             heterogeneous=bool(seed % 2))
    p = pack(inst, pad_tasks=16)
    cum = _trace_cum(rng)[None]
    frozen = jnp.asarray(rng.random((1, p.T)) < 0.25)
    for fz in (None, frozen):
        _check_table_equals_gather(rng, [p], cum, slack, fz)


@pytest.mark.parametrize("seed", [0, 1])
def test_timing_sweep_table_equals_gather_paper_m10(seed):
    """The same at the paper's 10-server shape: 40 tasks on 10 machines,
    400 rows, a 1500-epoch window, two instances.  On the CPU both forms
    are exact whatever the table's layout; what the chip's compiler does
    with the table is held by ``tests/test_tpu_compile.py``."""
    rng = np.random.default_rng(seed)
    insts = [pack(generate_instance(rng, n_jobs=10, k_tasks=4,
                                    n_machines=10), pad_tasks=40)
             for _ in range(2)]
    assert insts[0].T * insts[0].M == 400
    cum = jnp.stack([_trace_cum(rng, 1500) for _ in insts])
    _check_table_equals_gather(rng, insts, cum, 30)


def _timing_sweep_np(dur, pred, real, start, assign, cum, deadline, sweeps,
                     frozen):
    """A plain serial timing sweep, written apart from the jnp one.  Each
    sweep takes the real tasks by descending ``(start, index)`` as at its
    beginning; each task not pinned moves, within its window, to the
    start of least ``cum[min(s + d, H)] - cum[s]``, earliest among equals.
    The window starts at its start and ends where it would overrun its
    first real successor, the next real task on its machine, or the
    deadline."""
    T, H = len(start), len(cum) - 1
    start = np.array(start, np.int64)
    d = dur[np.arange(T), assign]
    for _ in range(sweeps):
        key = start * T + np.arange(T)
        for t in sorted(np.flatnonzero(real), key=lambda t: -key[t]):
            caps = [deadline]
            for v in np.flatnonzero(real):
                if pred[v, t] or (assign[v] == assign[t] and key[v] > key[t]):
                    caps.append(start[v])
            lo, hi = start[t], min(caps) - d[t]
            if frozen[t] or hi < lo:
                continue
            assert hi <= H, "the window must lie inside the trace"
            s = np.arange(lo, hi + 1)
            start[t] = lo + np.argmin(cum[np.minimum(s + d[t], H)] - cum[s])
    return start


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 10_000), slack=st.integers(0, 60))
def test_timing_sweep_equals_numpy_sweep(seed, slack):
    """The batched sweep (two instances of a heterogeneous fleet with
    padded tasks, 6 candidates each, under vmap as the solvers run it)
    returns the plain serial sweep's starts bit for bit, in both row
    sources (the table on a TPU, the gather elsewhere), for one and two
    sweeps, with and without pinned tasks."""
    rng = np.random.default_rng(seed)
    insts = [pack(generate_instance(rng, n_jobs=3, k_tasks=4, n_machines=4,
                                    heterogeneous=True), pad_tasks=16)
             for _ in range(2)]
    batch = stack_packed(insts)
    cum = jnp.stack([_trace_cum(rng) for _ in insts])
    prio = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    dec = jax.vmap(jax.vmap(sgs, in_axes=(None, 0)))(batch, prio)
    deadline = jnp.max(jax.vmap(jax.vmap(makespan, in_axes=(None, 0, 0)))(
        batch, dec.start, dec.assign), axis=1) + slack
    pinned = jnp.asarray(rng.random((2, 16)) < 0.3)
    for sweeps in (1, 2):
        for frozen in (None, pinned):
            for tpu in (False, True):
                with mock.patch("jax.default_backend",
                                return_value="tpu" if tpu else "cpu"):
                    _, got = _rows_and_sweeps(
                        batch, cum, dec.start, dec.assign, deadline,
                        frozen, sweeps=sweeps, tpu=tpu)
                for b, p in enumerate(insts):
                    fz = (np.zeros(16, bool) if frozen is None
                          else np.asarray(frozen[b]))
                    for c in range(6):
                        want = _timing_sweep_np(
                            np.asarray(p.dur), np.asarray(p.pred),
                            np.asarray(p.task_mask),
                            np.asarray(dec.start[b, c]),
                            np.asarray(dec.assign[b, c]), np.asarray(cum[b]),
                            int(deadline[b]), sweeps, fz)
                        assert np.array_equal(np.asarray(got[b, c]), want), (
                            sweeps, frozen is not None, tpu, b, c)


def test_upward_rank_tops_roots(rng):
    inst = generate_instance(rng, n_jobs=1, k_tasks=4, shape="chain")
    p = pack(inst)
    r = np.asarray(upward_rank(p))
    assert r[0] == r[:4].max()        # chain root has the longest path


# ---------------------------------------------------------------------------
# Objectives.
# ---------------------------------------------------------------------------

def test_objectives_hand_example():
    # 2 tasks chained on 1 machine: dur 2 then 3, intensity constant 100.
    job = Job(arrival=0, base_durations=(2, 3), edges=((0, 1),))
    inst = Instance(jobs=(job,), powers_kw=(2.0,), speeds=(1.0,))
    p = pack(inst)
    cum = jnp.asarray(constant(100.0, 50).cumulative())
    start = jnp.asarray([0, 2], jnp.int32)
    assign = jnp.zeros(2, jnp.int32)
    obj = evaluate(p, start, assign, cum)
    assert int(obj.makespan) == 5
    assert float(obj.energy) == pytest.approx(2.0 * 5 * 0.25)
    assert float(obj.carbon) == pytest.approx(2.0 * 5 * 0.25 * 100.0)
    assert float(utilization(p, start, assign)) == pytest.approx(1.0)


def test_violations_detects_each_constraint():
    job = Job(arrival=2, base_durations=(2, 2), edges=((0, 1),))
    inst = Instance(jobs=(job,), powers_kw=(1.0, 1.0), speeds=(1.0, 1.0))
    p = pack(inst)
    ok = jnp.asarray([2, 4], jnp.int32), jnp.asarray([0, 1], jnp.int32)
    assert int(violations(p, *ok)) == 0
    # arrival violation
    assert int(violations(p, jnp.asarray([0, 4], jnp.int32), ok[1])) > 0
    # dependency violation
    assert int(violations(p, jnp.asarray([2, 3], jnp.int32), ok[1])) > 0
    # overlap violation (same machine, same time)
    assert int(violations(p, jnp.asarray([2, 2], jnp.int32),
                          jnp.asarray([0, 0], jnp.int32))) > 0


# ---------------------------------------------------------------------------
# Solvers vs. the exact oracle (the CP-SAT stand-in).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heterogeneous", [True, False],
                         ids=["heterogeneous", "homogeneous"])
def test_solver_reaches_exact_makespan(heterogeneous, rng):
    inst = generate_instance(np.random.default_rng(7), n_jobs=2, k_tasks=2,
                             n_machines=2, heterogeneous=heterogeneous,
                             arrival_horizon=1)
    p = pack(inst)
    opt = exact_makespan(p)
    cum = _trace_cum(np.random.default_rng(7))
    out = solve_sa(p, cum, jnp.int32(1 << 27), jax.random.key(1),
                   objective="makespan", machine_rule="earliest_finish",
                   cfg=SAConfig(pop=64, iters=120))
    res = decode_full(p, cum, jnp.int32(1 << 27), out.prio, out.assign,
                      objective="makespan",
                      machine_rule="earliest_finish", sweeps=0)
    assert int(res.makespan) == opt


def test_bilevel_matches_exact_carbon_on_tiny():
    rng = np.random.default_rng(3)
    job = Job(arrival=0, base_durations=(2, 2), edges=((0, 1),))
    inst = Instance(jobs=(job,), powers_kw=(1.0, 1.0), speeds=(1.0, 1.0))
    p = pack(inst)
    tr = synthesize("AU-SA", days=2)
    cum_np = sample_window(tr, rng, 16).cumulative()
    cum = jnp.asarray(cum_np)
    res = solve_bilevel(p, cum, jax.random.key(0), objective="carbon",
                        stretch=2.0, cfg1=SAConfig(pop=64, iters=100),
                        cfg2=SAConfig(pop=64, iters=100))
    deadline = int(res.deadline)
    c_exact, _, _ = exact_carbon(p, cum_np, deadline)
    assert float(res.optimized.carbon) <= c_exact * 1.02 + 1e-6


def test_bilevel_invariants(rng):
    inst = generate_instance(np.random.default_rng(11), n_jobs=6, k_tasks=4,
                             n_machines=5, heterogeneous=True)
    p = pack(inst)
    cum = _trace_cum(np.random.default_rng(11), horizon=800)
    res = solve_bilevel(p, cum, jax.random.key(2), objective="carbon",
                        stretch=1.5, cfg1=SAConfig(pop=48, iters=60),
                        cfg2=SAConfig(pop=48, iters=60))
    # savings never negative (warm start guard), deadline respected
    assert float(res.carbon_savings) >= -1e-6
    assert int(res.optimized.makespan) <= int(res.deadline)
    assert not check_feasible_np(p, np.asarray(res.optimized.start),
                                 np.asarray(res.optimized.assign))


def test_constant_trace_carbon_equals_energy_times_intensity(rng):
    inst = generate_instance(np.random.default_rng(5), n_jobs=3, k_tasks=3)
    p = pack(inst)
    cum = jnp.asarray(constant(250.0, 600).cumulative())
    dec = sgs(p, upward_rank(p))
    c = float(carbon(p, dec.start, dec.assign, cum))
    e = float(energy(p, dec.assign))
    assert c == pytest.approx(e * 250.0, rel=1e-5)
