"""Pallas TPU kernel: batched FJSP schedule carbon evaluation.

The paper's solver hot spot after vectorization is *population fitness*:
for thousands of candidate schedules per instance, integrate each task's
emissions over the carbon trace (Def. 2.3).  With the cumulative-trace
trick each task costs ``P * (cum[s+d] - cum[s])`` — two gathers.  TPUs
have no vector gather, so the kernel turns each gather into a walk over
the horizon that *selects*:

    c1[i] = cum[h]  where e1[i] == h      (for h = 0 .. H)
    c0[i] = cum[h]  where e0[i] == h

Layout: the candidates' task slots are flattened and laid out lane-dense,
``[rows, 128]`` (one slot per lane), and tiled ``block_rows`` rows at a
time.  ``cum`` sits in SMEM in ``block_h``-epoch blocks along a second,
innermost grid axis, so each step reads ``cum[h]`` as a scalar and does
one vector compare-and-select per slot vreg — no gather, no cross-lane
reduction, and no one-hot matrix in memory.  The two selections carry
across horizon blocks in VMEM scratch; the last block writes
``c1 - c0``.  Any horizon fits: a year of 15-minute epochs (35k floats)
is 35 SMEM blocks of 1024 (the 1-D f32 tile, so a block is one tile).

Bit-exactness (the contract ``repro.kernels.ops.population_carbon`` is
property-tested under): every epoch in ``[0, H]`` is visited exactly once
and each clamped end epoch matches exactly one of them, so ``c1`` and
``c0`` *are* ``cum[e1]`` and ``cum[e0]`` — no arithmetic touches them —
and the one subtraction is the jnp path's ``cum[e1] - cum[e0]``.  The
wrapper applies the masked, power-weighted reduction in the *same
expression* as :func:`repro.core.objectives.carbon`, so the kernel path
equals the jnp gather path bitwise, not just allclose.  Start/end epochs
are clamped into ``[0, H]`` exactly as the jnp oracle clips them;
candidates overrunning the trace (routine for infeasible SA proposals
before the penalty prices them) integrate to the trace edge.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8
UNROLL = 8


def _kernel(start_ref, dur_ref, cum_ref, out_ref, c1_ref, c0_ref, *,
            block_h: int, horizon: int):
    """One (slot-block, horizon-block) step.

    start/dur: [block_rows, 128] i32 task slots; cum: [1, block_h] f32 in
    SMEM (epochs ``hb*block_h ..``); out: [block_rows, 128] f32 deltas,
    written on the last horizon block; c1/c0: VMEM scratch carrying the
    two selections across horizon blocks.
    """
    hb = pl.program_id(1)

    @pl.when(hb == 0)
    def _init():
        c1_ref[...] = jnp.zeros(c1_ref.shape, jnp.float32)
        c0_ref[...] = jnp.zeros(c0_ref.shape, jnp.float32)

    e0 = jnp.clip(start_ref[...], 0, horizon)
    e1 = jnp.clip(start_ref[...] + dur_ref[...], 0, horizon)
    base = hb * block_h

    def step(j, carry):
        c1, c0 = carry
        for k in range(UNROLL):          # Mosaic unrolls fully or not at all
            i = j * UNROLL + k
            h = base + i
            c = cum_ref[0, i]
            c1 = jnp.where(e1 == h, c, c1)
            c0 = jnp.where(e0 == h, c, c0)
        return c1, c0

    c1, c0 = jax.lax.fori_loop(0, block_h // UNROLL, step,
                               (c1_ref[...], c0_ref[...]))
    c1_ref[...] = c1
    c0_ref[...] = c0

    @pl.when(hb == pl.num_programs(1) - 1)
    def _finish():
        out_ref[...] = c1 - c0


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_h", "interpret"))
def schedule_delta_pallas(start: jnp.ndarray, dur: jnp.ndarray,
                          cum: jnp.ndarray, *, interpret: bool,
                          block_rows: int = 32,
                          block_h: int = 1024) -> jnp.ndarray:
    """start/dur [Pop, T] i32; cum [H+1] f32.  Returns the per-task trace
    deltas ``cum[clip(s+d)] - cum[clip(s)]`` as [Pop, T] f32.

    Flattens the Pop*T slots into lane-dense rows (padded to whole
    blocks) and pads ``cum`` to whole horizon blocks; end epochs are
    clamped to the real horizon ``H`` (never the padding), matching
    :func:`repro.core.objectives.carbon`'s clipping bit-exactly.

    ``interpret`` is **required**: callers go through
    :mod:`repro.kernels.ops`, where the backend-aware default lives
    (``interpret=True`` emulates the kernel body on CPU — the validation
    mode — ``interpret=False`` compiles for TPU).
    """
    P, T = start.shape
    n = P * T
    rows = -(-n // LANE)
    br = min(block_rows, -(-rows // SUBLANE) * SUBLANE)
    rows_p = -(-rows // br) * br
    H1 = cum.shape[0]
    bh = min(block_h, -(-H1 // LANE) * LANE)
    Hp = -(-H1 // bh) * bh

    def slots(a):
        a = jnp.pad(a.reshape(n).astype(jnp.int32), (0, rows_p * LANE - n))
        return a.reshape(rows_p, LANE)

    # [blocks, 1, bh]: the trailing (1, bh) block equals the array's own
    # trailing dims, which keeps the SMEM block legal when vmap prepends
    # an instance axis.
    cump = jnp.pad(cum.astype(jnp.float32), (0, Hp - H1)).reshape(
        Hp // bh, 1, bh)
    kernel = functools.partial(_kernel, block_h=bh, horizon=H1 - 1)
    slot_spec = pl.BlockSpec((br, LANE), lambda r, h: (r, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rows_p // br, Hp // bh),
        in_specs=[
            slot_spec,
            slot_spec,
            pl.BlockSpec((None, 1, bh), lambda r, h: (h, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=slot_spec,
        out_shape=jax.ShapeDtypeStruct((rows_p, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((br, LANE), jnp.float32)] * 2,
        interpret=interpret,
    )(slots(start), slots(dur), cump)
    return out.reshape(rows_p * LANE)[:n].reshape(P, T)
