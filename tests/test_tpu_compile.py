"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned slices, illegal block shapes, SMEM misuse.
These tests compile each kernel, ahead of time, for one chip of a
*described* ``v5e:2x2`` topology — the TPU compiler is installed, no chip
is needed — and check that the program holds the Mosaic kernel
(``tpu_custom_call``).  Widths are the ones users run: the paper's
96-candidate x 40-task population over a 1500-epoch window and over a
366-day trace, the stream engine's 1216-epoch gate with 96-epoch windows,
and both again under ``vmap`` as the batched solvers and policy sweeps
call them.  Five more compile the bound's serial scans: the timing sweep
selects its start-cost rows without a per-candidate gather, its scan
reads and writes with no gather or scatter at all, its table is one
array of bf16 parts with no bf16 round trip for the chip to skip, the
bound's sweep writes its argmins in whole 8-instance tiles, and SGS
places its tasks with no gather or scatter at all.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import generate_instance, pack, stack_packed
from repro.core.decoder import sgs, sweep_table, timing_sweep
from repro.kernels import ops
from repro.kernels.gate_quantile import gate_quantile_stats_pallas
from repro.kernels.schedule_eval import schedule_delta_pallas

POP, TASKS = 96, 40
YEAR = 366 * 96
GATE_EPOCHS, GATE_WINDOW = 1216, 96


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("horizon", [1500, YEAR], ids=["1500", "366d"])
def test_schedule_delta_compiles(one_chip, horizon):
    _assert_kernel(
        functools.partial(schedule_delta_pallas, interpret=False),
        _spec(one_chip, (POP, TASKS), jnp.int32),
        _spec(one_chip, (POP, TASKS), jnp.int32),
        _spec(one_chip, (horizon + 1,), jnp.float32))


def test_gate_quantile_compiles(one_chip):
    _assert_kernel(
        functools.partial(gate_quantile_stats_pallas,
                          max_window=GATE_WINDOW, interpret=False),
        _spec(one_chip, (GATE_EPOCHS,), jnp.float32),
        _spec(one_chip, (GATE_EPOCHS,), jnp.float32),
        _spec(one_chip, (), jnp.int32))


def test_population_carbon_batched_compiles(one_chip):
    """vmap over instances (``solve_bilevel_batch``) prepends a grid axis
    to every block, the SMEM trace block included."""
    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=5), pad_tasks=TASKS)
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (8,) + a.shape[1:], a.dtype),
        stack_packed([inst]))
    fn = jax.vmap(functools.partial(ops.population_carbon, interpret=False))
    _assert_kernel(fn, batch,
                   _spec(one_chip, (8, POP, TASKS), jnp.int32),
                   _spec(one_chip, (8, POP, TASKS), jnp.int32),
                   _spec(one_chip, (8, 1501), jnp.float32))


def test_gate_threshold_batched_compiles(one_chip):
    """vmap over a policy grid batches theta and the traced window too."""
    fn = jax.vmap(functools.partial(ops.gate_threshold,
                                    max_window=GATE_WINDOW, interpret=False))
    _assert_kernel(fn,
                   _spec(one_chip, (6, GATE_EPOCHS), jnp.float32),
                   _spec(one_chip, (6,), jnp.float32),
                   _spec(one_chip, (6,), jnp.int32))


def test_timing_sweep_selects_rows_without_gather(one_chip):
    """On a TPU the bound's timing sweep picks each candidate's start-cost
    row from the instance's table by a product; gathering H+1 epochs per
    candidate and step ran element by element and made the bound cost
    about 0.1 s per instance and fitness call."""
    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=5), pad_tasks=TASKS)
    B, H = 8, 1500
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (B,) + a.shape[1:], a.dtype),
        stack_packed([inst]))

    def sweep(inst, start, assign, cum, deadline):
        table = sweep_table(inst, cum)
        return jax.vmap(lambda s, a: timing_sweep(
            inst, s, a, cum, deadline, 2, table=table))(start, assign)

    with mock.patch("jax.default_backend", return_value="tpu"):
        text = jax.jit(jax.vmap(sweep)).lower(
            batch, _spec(one_chip, (B, POP, TASKS), jnp.int32),
            _spec(one_chip, (B, POP, TASKS), jnp.int32),
            _spec(one_chip, (B, H + 1), jnp.float32),
            _spec(one_chip, (B,), jnp.int32)).compile().as_text()
    assert not re.search(rf"f32\[{B},{POP},{H + 1}\]\S* gather\(", text)
    assert re.search(r" convolution\(", text)


@pytest.mark.parametrize("frozen", [False, True], ids=["free", "frozen"])
@pytest.mark.parametrize("machines", [5, 10])
def test_timing_sweep_scan_without_gather_or_scatter(one_chip, machines,
                                                     frozen):
    """Under vmap an index by each candidate's own task (its duration,
    successors, machine, key and start) becomes a gather and the write of
    its new start a scatter, which a TPU runs element by element: 14
    gathers and 2 scatters in the loop made up most of the sweep's time in
    the bound.  Every step reads and writes through one-hot selects
    instead, on the paper's fleets and with pinned tasks too."""
    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=machines),
                pad_tasks=TASKS)
    B, H = 32, 1500
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (B,) + a.shape[1:], a.dtype),
        stack_packed([inst]))

    def sweep(inst, start, assign, cum, deadline, fz):
        table = sweep_table(inst, cum)
        return jax.vmap(lambda s, a: timing_sweep(
            inst, s, a, cum, deadline, 2, frozen=fz if frozen else None,
            table=table))(start, assign)

    with mock.patch("jax.default_backend", return_value="tpu"):
        text = jax.jit(jax.vmap(sweep)).lower(
            batch, _spec(one_chip, (B, POP, TASKS), jnp.int32),
            _spec(one_chip, (B, POP, TASKS), jnp.int32),
            _spec(one_chip, (B, H + 1), jnp.float32),
            _spec(one_chip, (B,), jnp.int32),
            _spec(one_chip, (B, TASKS), jnp.bool_)).compile().as_text()
    comps = _called(text)
    loop = [ln for ln in text.splitlines() if "timing_sweep/while" in ln]
    assert any(_holds(comps, ln, r" convolution\(") for ln in loop)
    indexed = [ln.split(" = ")[0].strip() for ln in loop
               if _holds(comps, ln, r" (?:gather|scatter)\(")]
    assert not indexed, indexed


def test_sweep_table_is_one_array_cut_by_bit_masks(one_chip):
    """At the paper's 10-server shape (400 rows) the table is one
    ``bf16[3, T*M, H+1]`` array per instance, its parts cut from each f32
    row by bit masks: no convert from bf16 back to f32.  A split by
    rounding (``x - f32(bf16(x))``) the chip ran in f32, which emptied
    every row's low parts, and parts held as three arrays the chip read
    wrong for half of each 8-instance block: half of the batch's phase-2
    searches never improved."""
    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=10), pad_tasks=TASKS)
    B, H = 32, 1500
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (B,) + a.shape[1:], a.dtype),
        stack_packed([inst]))
    with mock.patch("jax.default_backend", return_value="tpu"):
        lowered = jax.jit(jax.vmap(sweep_table)).lower(
            batch, _spec(one_chip, (B, H + 1), jnp.float32))
    assert lowered.out_info.shape == (B, 3, TASKS * 10, H + 1)
    ops = {}
    for name, dtype, opcode, args in re.findall(
            r"%(\S+) = (\w+)\[[^\]]*\]\S* ([\w-]+)\(([^)]*)\)",
            lowered.compile().as_text()):
        ops[name] = (dtype, opcode, re.findall(r"%(\S+?)(?:[,\s]|$)", args))
    assert any(d == "u32" and op == "and" for d, op, _ in ops.values())
    round_trips = [name for name, (d, op, args) in ops.items()
                   if (d, op) == ("f32", "convert") and any(
                       ops.get(a, ("", "", []))[:2] == ("bf16", "convert")
                       for a in args)]
    assert not round_trips


def _called(text: str) -> dict:
    """Each computation of a compiled HLO text: name -> its body."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) \(", line)
        if head:
            cur = comps[head.group(1)] = []
        elif cur is not None:
            cur.append(line)
    return {name: "\n".join(body) for name, body in comps.items()}


def _holds(comps: dict, text: str, pattern: str, seen: tuple = ()) -> bool:
    """Whether HLO text (a line, or a computation's body), or any
    computation it calls, transitively, matches ``pattern``."""
    return bool(re.search(pattern, text)) or any(
        _holds(comps, comps.get(c, ""), pattern, seen + (c,))
        for c in re.findall(r"calls=%([\w.\-]+)", text) if c not in seen)


@pytest.mark.parametrize("machines", [5, 10])
def test_bound_sweep_argmin_steps_whole_tiles(one_chip, machines):
    """The bound's own program on the paper's fleets (32 instances, 200
    or 400 start-cost rows): the sweep's loop holds the argmin of each
    candidate's start-cost row (outputs ``s32[32, 96]``, tiled 8
    instances x 128 candidates), and every fusion of the loop that writes
    such an output steps over the instances 8 at a time or in multiples
    of 8 (its first iterated dimension, which its windows show to be the
    instances).  On a TPU
    v5e a fusion of the row product and its argmin stepping 4 or 2 at a
    time returned wrong argmins, mostly 0, for every window but the last
    of each 8-instance tile: half of the 10-server batch's phase-2
    searches never improved.  The sweep keeps the argmin out of the
    product's fusion, and this holds the fusion it lands in to whole
    tiles too."""
    from repro.core.solvers import solve_bilevel_batch
    from repro.core.solvers.annealing import SAConfig

    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=machines),
                pad_tasks=TASKS)
    B, H = 32, 1500
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (B,) + a.shape[1:], a.dtype),
        stack_packed([inst]))
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), B))
    sa = SAConfig(pop=POP, iters=150, sweeps=2)
    fn = functools.partial(solve_bilevel_batch, objective="carbon",
                           stretch=1.0, cfg1=sa, cfg2=sa)
    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch.object(ops, "on_tpu", return_value=True):
        text = jax.jit(fn).lower(
            batch, _spec(one_chip, (B, H + 1), jnp.float32),
            _spec(one_chip, keys.shape, keys.dtype)).compile().as_text()
    comps = _called(text)
    writes = [ln for ln in text.splitlines()
              if "timing_sweep/while" in ln and " fusion(" in ln
              and re.search(rf"= \(?.*s32\[{B},{POP}\]",
                            ln.split(" fusion(")[0])]
    # An argmin: a reduce whose outputs end in the indices, s32[B, POP].
    assert any(_holds(comps, ln, rf"s32\[{B},{POP}\]\S*\) reduce\(")
               for ln in writes)
    steps = {}
    for ln in writes:
        name = ln.split(" = ")[0].strip()
        iters = [int(n) for n in re.search(
            r'"iteration_bounds":\[([^]]*)\]', ln).group(1)
            .replace('"', "").split(",")]
        # The first iterated dimension must be the instances: each window
        # times its step count covers B instances, or B // 8 tiles of 8.
        for kind in ("kernel", "output"):
            window = re.search(rf'"{kind}_window_bounds":\["(\d+)"', ln)
            if window:
                covers = int(window.group(1)) * iters[0]
                assert covers in (B, B // 8), (name, kind, covers)
        steps[name] = B // iters[0] if B % iters[0] == 0 else 0
    assert steps
    assert all(step % 8 == 0 and step for step in steps.values()), steps


@pytest.mark.parametrize("rule", ["earliest_finish", "fixed"])
def test_sgs_places_without_gather_or_scatter(one_chip, rule):
    """Under vmap an index by each candidate's task or machine becomes a
    gather or scatter, which a TPU runs element by element: they made up
    nearly all of SGS's device time in the bound.  Every step reads and
    writes through one-hot selects instead."""
    inst = pack(generate_instance(np.random.default_rng(0), n_jobs=10,
                                  k_tasks=4, n_machines=5), pad_tasks=TASKS)
    B = 8
    batch = jax.tree.map(
        lambda a: _spec(one_chip, (B,) + a.shape[1:], a.dtype),
        stack_packed([inst]))
    fn = jax.vmap(jax.vmap(functools.partial(sgs, machine_rule=rule),
                           in_axes=(None, 0, 0)))
    text = jax.jit(fn).lower(
        batch, _spec(one_chip, (B, POP, TASKS), jnp.float32),
        _spec(one_chip, (B, POP, TASKS), jnp.int32)).compile().as_text()
    assert re.search(r" while\(", text)
    assert not re.search(r" (?:gather|scatter)\(", text)
