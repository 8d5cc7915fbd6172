"""Sharded structure sweep: both of its XLA programs split over instances.

:func:`~repro.scenarios.sweep.sweep_structure` runs the whole family x
size x server-count x fleet grid as two XLA programs — the gated online
dispatch sweep and the offline SA bi-level bound.  This module shards both
over the instance axis:

* :func:`bilevel_sharded` — :func:`repro.core.solvers.bilevel.
  solve_bilevel_batch` with rows (instances, traces, PRNG keys) sharded;
* :func:`sweep_sharded` — the full structure sweep on ``devices`` devices,
  a thin veneer over ``sweep_structure(devices=...)`` (which routes its
  dispatch / bound / learn programs through this package), so benchmarks
  and tests have one sharded front door.

Bit-exact with the single-device sweep on the CPU: per-row SA chains are
driven by per-row keys and rows never interact.  Unlike the dispatch/train paths,
the bound does **not** go through ``shard_map``: XLA's manual-partitioning
pipeline fuses transcendentals (the ``erf_inv`` behind
``jax.random.normal``) a vector-ulp differently than the plain jit path,
and a one-ulp fitness difference can flip a stochastic-search
accept/reject and diverge the whole SA trajectory.  Instead each device
runs the *same compiled batched program* on its committed row shard —
per-device program dispatch, which is asynchronous in JAX, so shards still
execute concurrently — and on the CPU the program is batch-size
independent (``tests/test_shard.py`` locks that parity too).  On a TPU v5e
it is not: the full structure-sweep grid's bound at 60 rows on each of 4
chips and at 240 rows on one chip gave a different savings figure in
every cell, while the dispatch rows matched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.instance import PackedInstance
from repro.core.solvers.bilevel import BilevelResult, solve_bilevel_batch
from repro.shard.batch import _pad_rows, instance_mesh, round_up


def bilevel_shards(insts: PackedInstance, cums, keys,
                   devices: int | None = None,
                   processes: int | None = None,
                   **kw) -> list[BilevelResult]:
    """Dispatch ``solve_bilevel_batch`` once per device of this process.

    Rows are padded to a device multiple (inert instances, zero keys) and
    each device solves its contiguous block of rows with the identical
    compiled program (see the module docstring for why this path
    dispatches per device instead of shard_mapping).  Returns this
    process's per-device results, each still on its device, in row order;
    :func:`bilevel_sharded` gathers them.
    """
    mesh = instance_mesh(devices, processes=processes)
    B = int(jnp.asarray(cums).shape[0])
    rows = round_up(B, int(mesh.size))
    pad = rows - B
    if pad:
        kd = jax.random.key_data(keys)
        keys = jax.random.wrap_key_data(jnp.concatenate(
            [kd, jnp.zeros((pad,) + kd.shape[1:], kd.dtype)]))
    insts_p = _pad_rows(insts, rows)
    cums_p = _pad_rows(cums, rows)
    if processes is None:
        devs = list(mesh.devices.ravel())
        base = 0
    else:
        # Canonical id order, independent of process_order / spawn order:
        # process p owns rows [p*rows/P, (p+1)*rows/P) on its mesh-local
        # devices.
        pid = jax.process_index()
        devs = [d for d in mesh.devices.ravel() if d.process_index == pid]
        base = pid * (rows // jax.process_count())
    per = rows // int(mesh.size)
    shards = []
    for i, dev in enumerate(devs):
        sl = slice(base + i * per, base + (i + 1) * per)
        args = jax.tree.map(lambda x: jax.device_put(x[sl], dev),
                            (insts_p, cums_p, keys))
        shards.append(solve_bilevel_batch(*args, **kw))   # async, on dev i
    return shards


def bilevel_sharded(insts: PackedInstance, cums, keys,
                    devices: int | None = None,
                    processes: int | None = None, **kw) -> BilevelResult:
    """``solve_bilevel_batch`` with the instance axis sharded.

    ``keys`` is the same ``[B]`` typed-key array the batched solver takes;
    :func:`bilevel_shards` solves each device's block of rows, and the
    results come back concatenated in row order, sliced to the real rows.

    With ``processes=P`` (``devices`` per process) each process dispatches
    only the contiguous row block its canonical process id owns — the same
    per-device pattern, one level up — then
    ``multihost_utils.process_allgather`` concatenates the blocks in
    process-id order, which *is* canonical row order.  Each device still
    runs the identical compiled program on identically-shaped shards, so
    the SA trajectories — and therefore the bound — are bit-exact at any
    (process count, device count) with the same total.
    """
    B = int(jnp.asarray(cums).shape[0])
    shards = bilevel_shards(insts, cums, keys, devices=devices,
                            processes=processes, **kw)
    out = jax.tree.map(lambda *xs: np.concatenate(
        [np.asarray(x) for x in xs]), *shards)
    if processes is not None:
        from jax.experimental import multihost_utils
        out = multihost_utils.process_allgather(out, tiled=True)
    out = jax.tree.map(lambda x: x[:B], out)
    return jax.tree.map(jnp.asarray, out)


def sweep_sharded(spec, offline: bool = True, learn=None,
                  devices: int | None = None,
                  processes: int | None = None):
    """The full structure sweep, sharded: ``(rows, meta)`` as
    :func:`~repro.scenarios.sweep.sweep_structure` returns them, bit-exact
    with the single-device sweep.  ``devices=None`` uses every local
    device (every device per process when ``processes=P``)."""
    from repro.scenarios.sweep import sweep_structure   # lazy: avoids cycle
    from repro.shard.batch import device_count
    if processes is not None:
        return sweep_structure(spec, offline=offline, learn=learn,
                               devices=devices, processes=processes)
    return sweep_structure(spec, offline=offline, learn=learn,
                           devices=devices or device_count())
