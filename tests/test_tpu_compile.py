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
call them.  Two more compile the bound's serial scans: the timing sweep
selects its start-cost rows without a per-candidate gather, and SGS
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
