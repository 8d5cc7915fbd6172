"""Pallas kernels vs. jnp paths: **bit-exactness** contracts + shape sweeps.

The carbon-eval and gate-quantile kernels are not allclose targets — their
contract is ``kernel path == jnp path`` bitwise in f32 (docs/kernels.md),
property-tested here across every scenario family x fleet x machine rule,
in every interpret mode available on the host, including ``pack_aligned``
padded batches, frozen-prefix (rolling) candidates, and candidates that
overrun the carbon trace (the regression the pre-fix kernel failed: zero
padding on ``cum`` gave wrong, even negative, deltas).

A seed-pinned tiny ``solve_bilevel`` run is additionally golden-locked in
``tests/golden/sa_bilevel_tiny.json`` and re-run with the kernel fitness
path enabled — the stored golden must hold *unchanged* on both paths
(regenerate with ``PYTHONPATH=src python tests/test_kernels.py --write``
and explain the shift in the PR, same convention as
``test_structure_golden.py``).

The flash-attention / SSD kernels keep their original allclose sweeps
(softmax reductions genuinely reassociate there).
"""
import functools
import inspect
import json
import os
import sys

import numpy as np
import pytest
from numpy.testing import assert_allclose

import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # script mode (--write) without pytest:
    import conftest  # noqa: F401  — installs the hypothesis stub
    from hypothesis import given, settings, strategies as st

from repro.core import generate_instance, pack, synthesize
from repro.core.carbon import sample_window
from repro.core.instance import stack_packed
from repro.core.decoder import MACHINE_RULES, sgs
from repro.core.objectives import carbon, task_durations
from repro.core.solvers import common
from repro.core.solvers.annealing import SAConfig, solve_sa
from repro.core.solvers.bilevel import solve_bilevel
from repro.core.solvers.online_jax import (dirty_mask, quantile_threshold,
                                           sorted_windows)
from repro.kernels import ops
from repro.kernels.gate_quantile import gate_quantile_stats_pallas
from repro.kernels.ref import (attention_ref, gate_threshold_ref,
                               schedule_carbon_ref, ssd_ref)
from repro.kernels.schedule_eval import schedule_delta_pallas
from repro.scenarios import FAMILY_NAMES, FLEET_NAMES
from tests.strategies import scenario_case, seeds

GOLDEN_PATH = os.path.join(os.path.dirname(__file__), "golden",
                           "sa_bilevel_tiny.json")

# Every interpret mode runnable on this host: the interpreter everywhere,
# compiled Mosaic only on a real TPU.  Tests sweep all of them so the TPU
# CI run covers compiled-vs-jnp with the same cases.
INTERPRET_MODES = ([True, False] if jax.default_backend() == "tpu"
                   else [True])


def _exact(a, b, ctx=""):
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype, f"{ctx}: dtype {a.dtype} != {b.dtype}"
    assert np.array_equal(a, b, equal_nan=True), (
        f"{ctx}: max abs diff {np.max(np.abs(a - b))} "
        f"at {np.unravel_index(np.argmax(a != b), a.shape)}")


def _population(rng, p, pop, horizon, overrun=False):
    """Random candidate (starts, assigns) with only *allowed* machines."""
    hi = 2 * horizon if overrun else max(horizon // 2, 2)
    lo = -5 if overrun else 0
    starts = jnp.asarray(rng.integers(lo, hi, (pop, p.T)), jnp.int32)
    allowed = np.asarray(p.allowed)
    assigns = np.zeros((pop, p.T), np.int32)
    for t in range(p.T):
        choices = np.nonzero(allowed[t])[0]
        if len(choices):
            assigns[:, t] = rng.choice(choices, size=pop)
    return starts, jnp.asarray(assigns)


# ---------------------------------------------------------------------------
# schedule_eval / population_carbon — bit-exact vs objectives.carbon
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILY_NAMES)
@pytest.mark.parametrize("fleet", FLEET_NAMES)
def test_population_carbon_bit_exact(family, fleet):
    rng = np.random.default_rng(hash((family, fleet)) % 2**31)
    p, w = scenario_case(3, family=family, fleet=fleet, horizon=300)
    cum = jnp.asarray(w.cumulative())
    starts, assigns = _population(rng, p, 9, 300, overrun=True)
    ref = jax.vmap(lambda s, a: carbon(p, s, a, cum))(starts, assigns)
    for interpret in INTERPRET_MODES:
        got = ops.population_carbon(p, starts, assigns, cum,
                                    interpret=interpret)
        _exact(ref, got, f"{family}/{fleet}/interpret={interpret}")


@pytest.mark.parametrize("pop,pad,horizon", [(3, 10, 100), (17, 30, 500),
                                             (64, 64, 257), (8, 130, 640)])
def test_population_carbon_shapes(pop, pad, horizon):
    """The original shape sweep, upgraded from allclose to exact."""
    rng = np.random.default_rng(pop)
    inst = generate_instance(rng, n_jobs=4, k_tasks=2, n_machines=5,
                             heterogeneous=True)
    p = pack(inst, pad_tasks=pad)
    tr = synthesize("CAL", days=10)
    cum = jnp.asarray(sample_window(tr, rng, horizon).cumulative())
    starts = jnp.asarray(rng.integers(0, horizon // 2, (pop, p.T)),
                         jnp.int32)
    assigns = jnp.asarray(rng.integers(0, 5, (pop, p.T)), jnp.int32)
    out = ops.population_carbon(p, starts, assigns, cum, interpret=True)
    dur = jax.vmap(lambda a: task_durations(p, a))(assigns)
    power = p.power[assigns] * p.task_mask[None, :]
    ref = schedule_carbon_ref(starts, dur, power.astype(jnp.float32), cum)
    _exact(ref, out, f"shapes {pop}/{pad}/{horizon}")


def test_population_carbon_overrun_regression():
    """Candidates ending at or past H+1 must integrate to the trace edge.

    The pre-fix kernel zero-padded ``cum`` to a lane multiple without
    clamping ``e1``, so an overrunning candidate read ``cum[e1] = 0`` and
    produced a *negative* carbon delta — this test fails on that kernel.
    """
    rng = np.random.default_rng(0)
    p, w = scenario_case(1, family="chain", fleet="homog", horizon=120)
    cum = jnp.asarray(w.cumulative())
    H = cum.shape[0] - 1
    # Every candidate deliberately ends past the horizon (starts near/past
    # H), several land inside the lane-padding region [H+1, 128).
    starts = jnp.asarray(
        rng.integers(H - 2, H + 40, (8, p.T)), jnp.int32)
    _, assigns = _population(rng, p, 8, H)
    got = ops.population_carbon(p, starts, assigns, cum, interpret=True)
    ref = jax.vmap(lambda s, a: carbon(p, s, a, cum))(starts, assigns)
    _exact(ref, got, "overrun")
    # And the fixed semantics: overrunning work costs >= 0 carbon, and a
    # task straddling the edge integrates exactly to cum[H].
    assert np.all(np.asarray(got) >= 0.0)
    one_start = jnp.full((1, p.T), H - 1, jnp.int32)
    one = ops.population_carbon(p, one_start, assigns[:1], cum,
                                interpret=True)
    expect = jax.vmap(lambda s, a: carbon(p, s, a, cum))(one_start,
                                                         assigns[:1])
    _exact(expect, one, "edge-straddle")


def test_population_carbon_pack_aligned_padding_inert():
    """Mixed-shape batches through pack_aligned: padded tasks/machines must
    not move the kernel's carbon (the PackedInstance padding contract)."""
    from repro.scenarios import ScenarioConfig, pack_aligned, sample_batch
    rng = np.random.default_rng(11)
    insts = (sample_batch(rng, ScenarioConfig(
        family="diamond", fleet="mixed", n_jobs=2, width=2, depth=2,
        n_machines=3), 2)
        + sample_batch(rng, ScenarioConfig(
            family="chain", fleet="homog", n_jobs=4, width=1, depth=3,
            n_machines=2), 2))
    batch = pack_aligned(insts)
    tr = synthesize("AU-SA", days=10)
    cum = jnp.asarray(sample_window(tr, np.random.default_rng(1),
                                    400).cumulative())
    for i in range(len(insts)):
        p = jax.tree.map(lambda a: a[i], batch)
        starts, assigns = _population(rng, p, 6, 400, overrun=True)
        ref = jax.vmap(lambda s, a: carbon(p, s, a, cum))(starts, assigns)
        got = ops.population_carbon(p, starts, assigns, cum, interpret=True)
        _exact(ref, got, f"pack_aligned[{i}]")


@settings(max_examples=20, deadline=None, derandomize=True)
@given(seed=seeds(), rule=st.sampled_from(MACHINE_RULES))
def test_population_carbon_property(seed, rule):
    """Property: kernel == vmap(objectives.carbon) on *decoded* (SGS)
    populations across drawn families x fleets x machine rules."""
    p, w = scenario_case(seed, family=FAMILY_NAMES[seed % len(FAMILY_NAMES)],
                         fleet=FLEET_NAMES[seed % len(FLEET_NAMES)],
                         horizon=350)
    cum = jnp.asarray(w.cumulative())
    rng = np.random.default_rng(seed)
    prio = jnp.asarray(rng.normal(size=(5, p.T)), jnp.float32)
    _, assigns = _population(rng, p, 5, 350)
    dec = jax.vmap(lambda pr, a: sgs(p, pr, a, machine_rule=rule))(prio,
                                                                   assigns)
    ref = jax.vmap(lambda s, a: carbon(p, s, a, cum))(dec.start, dec.assign)
    got = ops.population_carbon(p, dec.start, dec.assign, cum,
                                interpret=True)
    _exact(ref, got, f"seed={seed} rule={rule}")


# ---------------------------------------------------------------------------
# gate_quantile / gate_threshold — bit-exact vs online_jax internals
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("E,W,theta", [
    (300, 48, 0.3), (257, 96, 0.5), (64, 24, 0.9), (100, 1, 0.25),
    (130, 130, 0.6), (16, 96, 0.0), (200, 48, 1.0),
])
def test_gate_threshold_bit_exact(E, W, theta):
    rng = np.random.default_rng(E * 1000 + W)
    inten = jnp.asarray(rng.uniform(50, 900, E), jnp.float32)
    inten = inten.at[::7].set(inten[0])          # inject ties
    sv, n = sorted_windows(inten, jnp.int32(W), W)
    ref = quantile_threshold(sv, n, jnp.float32(theta))
    naive = gate_threshold_ref(inten, jnp.float32(theta), jnp.int32(W), W)
    for interpret in INTERPRET_MODES:
        got = ops.gate_threshold(inten, jnp.float32(theta), jnp.int32(W), W,
                                 interpret=interpret)
        _exact(ref, got, f"E={E} W={W} th={theta} interp={interpret}")
        _exact(naive, got, f"vs-ref E={E} W={W} th={theta}")


def test_gate_threshold_vector_theta():
    """Per-epoch theta vectors (forecast-conditioned gates) stay exact."""
    rng = np.random.default_rng(5)
    E, W = 220, 48
    inten = jnp.asarray(rng.uniform(50, 900, E), jnp.float32)
    theta = jnp.asarray(rng.uniform(0, 1, E), jnp.float32)
    sv, n = sorted_windows(inten, jnp.int32(W), W)
    ref = quantile_threshold(sv, n, theta)
    got = ops.gate_threshold(inten, theta, jnp.int32(W), W, interpret=True)
    _exact(ref, got, "vector-theta")


def test_gate_stats_are_order_statistics():
    """The kernel's (a, b) are bitwise the sorted-window positions the jnp
    path gathers — selection, not arithmetic."""
    rng = np.random.default_rng(9)
    E, W, theta = 140, 32, 0.37
    inten = jnp.asarray(rng.uniform(50, 900, E), jnp.float32)
    inten = inten.at[::3].set(inten[1])
    a, b, n = gate_quantile_stats_pallas(
        inten, jnp.full((E,), theta, jnp.float32), jnp.int32(W),
        max_window=W, interpret=True)
    sv, n_ref = sorted_windows(inten, jnp.int32(W), W)
    _exact(n_ref, n, "valid count")
    vi = jnp.float32(theta) * (n_ref - 1).astype(jnp.float32)
    lo_i = jnp.floor(vi).astype(jnp.int32)
    hi_i = jnp.minimum(lo_i + 1, n_ref - 1)
    _exact(jnp.take_along_axis(sv, lo_i[:, None], 1)[:, 0], a, "a")
    _exact(jnp.take_along_axis(sv, hi_i[:, None], 1)[:, 0], b, "b")


@settings(max_examples=25, deadline=None, derandomize=True)
@given(seed=seeds(), theta=st.floats(0.0, 1.0), window=st.integers(1, 120))
def test_dirty_mask_property(seed, theta, window):
    """Property: the wired gate switch produces identical dirty masks."""
    rng = np.random.default_rng(seed)
    E = 120 + seed % 200
    inten = jnp.asarray(rng.uniform(30, 950, E), jnp.float32)
    ref = dirty_mask(inten, jnp.float32(theta), jnp.int32(window),
                     max_window=window, use_kernels=False)
    got = dirty_mask(inten, jnp.float32(theta), jnp.int32(window),
                     max_window=window, use_kernels=True)
    _exact(ref, got, f"seed={seed} th={theta} w={window}")


# ---------------------------------------------------------------------------
# population_fitness — the wired SA hot loop, kernel == jnp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("objective", ["carbon", "energy"])
@pytest.mark.parametrize("rule", MACHINE_RULES)
def test_population_fitness_paths_equal(objective, rule):
    rng = np.random.default_rng(21)
    p, w = scenario_case(7, family="layered", fleet="tiered", horizon=400)
    cum = jnp.asarray(w.cumulative())
    prio = jnp.asarray(rng.normal(size=(6, p.T)), jnp.float32)
    _, assign = _population(rng, p, 6, 400)
    deadline = jnp.int32(180)
    ref = common.population_fitness(p, cum, deadline, prio, assign,
                                    objective, rule, 2, use_kernels=False)
    got = common.population_fitness(p, cum, deadline, prio, assign,
                                    objective, rule, 2, use_kernels=True)
    _exact(ref, got, f"{objective}/{rule}")


def test_population_fitness_frozen_prefix():
    """Rolling-replan candidates: frozen tasks pin the executed prefix; the
    kernel path must price them identically (the timing sweep skips them
    on both paths)."""
    rng = np.random.default_rng(31)
    p, w = scenario_case(13, family="fanout", fleet="mixed", horizon=400)
    cum = jnp.asarray(w.cumulative())
    prio = jnp.asarray(rng.normal(size=(5, p.T)), jnp.float32)
    _, assign = _population(rng, p, 5, 400)
    frozen = jnp.asarray(np.arange(p.T) < p.T // 3)
    ref = common.population_fitness(p, cum, jnp.int32(150), prio, assign,
                                    "carbon", "fixed", 2, frozen=frozen,
                                    use_kernels=False)
    got = common.population_fitness(p, cum, jnp.int32(150), prio, assign,
                                    "carbon", "fixed", 2, frozen=frozen,
                                    use_kernels=True)
    _exact(ref, got, "frozen")


@settings(max_examples=15, deadline=None, derandomize=True)
@given(seed=seeds(), rule=st.sampled_from(MACHINE_RULES),
       objective=st.sampled_from(("carbon", "energy")))
def test_population_fitness_property(seed, rule, objective):
    p, w = scenario_case(seed, family=FAMILY_NAMES[seed % len(FAMILY_NAMES)],
                         fleet=FLEET_NAMES[seed % len(FLEET_NAMES)],
                         horizon=320)
    cum = jnp.asarray(w.cumulative())
    rng = np.random.default_rng(seed)
    prio = jnp.asarray(rng.normal(size=(4, p.T)), jnp.float32)
    _, assign = _population(rng, p, 4, 320)
    deadline = jnp.int32(100 + seed % 150)
    ref = common.population_fitness(p, cum, deadline, prio, assign,
                                    objective, rule, 2, use_kernels=False)
    got = common.population_fitness(p, cum, deadline, prio, assign,
                                    objective, rule, 2, use_kernels=True)
    _exact(ref, got, f"seed={seed} {objective}/{rule}")


# ---------------------------------------------------------------------------
# solvers end to end — kernel fitness path reproduces identical solves
# ---------------------------------------------------------------------------

_SA_CFG = SAConfig(pop=16, iters=12, migrate_every=5)


def test_solve_sa_identical_under_kernels():
    p, w = scenario_case(17, family="diamond", fleet="tiered", horizon=400)
    cum = jnp.asarray(w.cumulative())
    key = jax.random.PRNGKey(0)
    ref = solve_sa(p, cum, jnp.int32(200), key, cfg=_SA_CFG,
                   use_kernels=False)
    got = solve_sa(p, cum, jnp.int32(200), key, cfg=_SA_CFG,
                   use_kernels=True)
    for r, g, name in zip(ref, got, ref._fields):
        _exact(r, g, f"solve_sa.{name}")


@pytest.mark.parametrize("objective", ["carbon", "energy"])
def test_solve_bilevel_batch_identical_under_kernels(objective):
    """The batch entry point (vmapped solve_bilevel — Pallas under vmap),
    under both objectives the kernel path combines."""
    from repro.core.solvers.bilevel import solve_bilevel_batch
    pt, pm = 32, 4
    p1, w1 = scenario_case(23, family="fanout", fleet="homog", horizon=400,
                           pad_tasks=pt, pad_machines=pm)
    p2, w2 = scenario_case(29, family="chain", fleet="mixed", horizon=400,
                           pad_tasks=pt, pad_machines=pm)
    batch = stack_packed([p1, p2])
    cums = jnp.stack([jnp.asarray(w1.cumulative()),
                      jnp.asarray(w2.cumulative())])
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    ref = solve_bilevel_batch(batch, cums, keys, objective=objective,
                              stretch=1.5, cfg1=_SA_CFG, use_kernels=False)
    got = solve_bilevel_batch(batch, cums, keys, objective=objective,
                              stretch=1.5, cfg1=_SA_CFG, use_kernels=True)
    for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        _exact(r, g, f"solve_bilevel_batch.{objective}")


# ---------------------------------------------------------------------------
# sa_bilevel_tiny golden — locked on BOTH fitness paths
# ---------------------------------------------------------------------------

def _sa_tiny_run(use_kernels):
    p, w = scenario_case(2024, family="layered", fleet="mixed", horizon=500)
    cum = jnp.asarray(w.cumulative())
    res = solve_bilevel(p, cum, jax.random.PRNGKey(42), stretch=1.5,
                        cfg1=SAConfig(pop=24, iters=30, migrate_every=10),
                        use_kernels=use_kernels)
    return {
        "opt_makespan": int(res.opt_makespan),
        "deadline": int(res.deadline),
        "baseline_carbon_g": float(res.baseline.carbon),
        "optimized_carbon_g": float(res.optimized.carbon),
        "optimized_makespan": int(res.optimized.makespan),
        "carbon_savings": float(res.carbon_savings),
        "optimized_start": np.asarray(res.optimized.start).tolist(),
        "optimized_assign": np.asarray(res.optimized.assign).tolist(),
    }


@pytest.mark.parametrize("use_kernels", [False, True])
def test_sa_bilevel_golden(use_kernels):
    """The stored SA golden must hold bit-exactly on both fitness paths —
    the 'goldens unchanged under REPRO_KERNELS=1' contract."""
    if not os.path.exists(GOLDEN_PATH):
        pytest.fail(f"golden file missing: {GOLDEN_PATH} — regenerate with "
                    "`PYTHONPATH=src python tests/test_kernels.py --write`")
    with open(GOLDEN_PATH) as f:
        want = json.load(f)["sa_bilevel_tiny"]
    got = _sa_tiny_run(use_kernels)
    for k in ("opt_makespan", "deadline", "optimized_makespan",
              "optimized_start", "optimized_assign"):
        assert got[k] == want[k], f"{k}: {got[k]!r} != golden {want[k]!r}"
    for k in ("baseline_carbon_g", "optimized_carbon_g", "carbon_savings"):
        # floats cross platforms: tight allclose, identical on like hosts
        assert_allclose(got[k], want[k], rtol=1e-5, err_msg=k)


# ---------------------------------------------------------------------------
# interpret-mode plumbing — the backend default lives in ops.py only
# ---------------------------------------------------------------------------

def test_kernels_require_explicit_interpret():
    """No kernel signature may default ``interpret`` (the silent-interpret
    bug: compiled callers falling back to the CPU interpreter on TPU)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ssd_scan import ssd_scan_pallas
    for fn in (schedule_delta_pallas, gate_quantile_stats_pallas,
               flash_attention_pallas, ssd_scan_pallas):
        wrapped = inspect.unwrap(fn, stop=lambda f: hasattr(f, "__wrapped__"))
        sig = inspect.signature(wrapped)
        param = sig.parameters["interpret"]
        assert param.default is inspect.Parameter.empty, \
            f"{fn.__name__} defaults interpret={param.default!r}"
        assert param.kind is inspect.Parameter.KEYWORD_ONLY


def test_kernels_enabled_resolution(monkeypatch):
    assert ops.kernels_enabled(True) is True
    assert ops.kernels_enabled(False) is False
    for val, want in [("1", True), ("true", True), ("ON", True),
                      ("yes", True), ("0", False), ("false", False),
                      ("off", False), ("No", False)]:
        monkeypatch.setenv("REPRO_KERNELS", val)
        assert ops.kernels_enabled() is want, val
        # explicit argument always wins over the env
        assert ops.kernels_enabled(not want) is (not want)
    monkeypatch.delenv("REPRO_KERNELS")
    assert ops.kernels_enabled() is ops.on_tpu()


# ---------------------------------------------------------------------------
# flash attention (allclose — softmax genuinely reassociates)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KVH,S,dh,causal,window,dtype", [
    (2, 4, 2, 128, 64, True, 0, jnp.float32),
    (1, 8, 8, 256, 32, True, 64, jnp.float32),
    (2, 2, 1, 128, 64, False, 0, jnp.float32),
    (1, 4, 4, 128, 128, True, 0, jnp.bfloat16),
    (1, 8, 2, 512, 64, True, 0, jnp.float32),
])
def test_flash_attention_kernel(B, H, KVH, S, dh, causal, window, dtype):
    q = jax.random.normal(jax.random.key(1), (B, H, S, dh)).astype(dtype)
    k = jax.random.normal(jax.random.key(2), (B, KVH, S, dh)).astype(dtype)
    v = jax.random.normal(jax.random.key(3), (B, KVH, S, dh)).astype(dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                    atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# ssd scan (allclose — chunked recurrence reassociates)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,P,G,N,chunk,dtype", [
    (2, 128, 4, 32, 2, 16, 32, jnp.float32),
    (1, 64, 2, 16, 1, 8, 16, jnp.float32),
    (1, 256, 8, 64, 1, 32, 64, jnp.float32),
    (2, 64, 4, 32, 4, 16, 32, jnp.bfloat16),
])
def test_ssd_scan_kernel(B, S, H, P, G, N, chunk, dtype):
    x = (0.5 * jax.random.normal(jax.random.key(4), (B, S, H, P))
         ).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(5), (B, S, H)))
    A = -jnp.exp(0.3 * jax.random.normal(jax.random.key(6), (H,)))
    Bm = 0.5 * jax.random.normal(jax.random.key(7), (B, S, G, N))
    Cm = 0.5 * jax.random.normal(jax.random.key(8), (B, S, G, N))
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hr = ssd_ref(x.astype(jnp.float32), dt, A, Bm, Cm)
    tol = 3e-4 if dtype == jnp.float32 else 3e-2
    assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32),
                    atol=tol, rtol=tol)
    assert_allclose(np.asarray(h), np.asarray(hr), atol=tol, rtol=tol)


def _write_golden():
    ref = _sa_tiny_run(use_kernels=False)
    kern = _sa_tiny_run(use_kernels=True)
    assert ref == kern, "kernel path diverged from jnp path at write time"
    record = {
        "_regenerate": "PYTHONPATH=src python tests/test_kernels.py --write",
        "sa_bilevel_tiny": ref,
    }
    os.makedirs(os.path.dirname(GOLDEN_PATH), exist_ok=True)
    with open(GOLDEN_PATH, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {GOLDEN_PATH}")


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    if "--write" in sys.argv:
        _write_golden()
    else:
        print(__doc__)
