"""Pallas TPU kernel: fused sorted-window quantile gate threshold.

The online dispatcher's second measured hot spot (after population
fitness) is the carbon gate: for every epoch ``t``, the ``theta``-quantile
of the forecast window ``intensity[t : t + window]`` decides whether ready
tasks wait (:func:`repro.core.solvers.online_jax.sorted_windows` +
:func:`~repro.core.solvers.online_jax.quantile_threshold`).  The jnp path
materializes and sorts an ``[E, W]`` window matrix in HBM; this kernel
fuses window construction, selection and the quantile interpolation into
one pass over the horizon with the windows resident in VMEM — the ``[E,
W]`` matrix never exists outside a block.

No sort: the interpolated quantile needs only *two order statistics* per
window (``floor(theta * (n-1))`` and its successor), so the kernel selects
them by stable rank counting —

    rank[w] = #{u : x[u] < x[w]}  +  #{u before w : x[u] == x[w]}

— an O(W^2) compare-and-count per window that is pure VPU work, needs no
sort network, and *selects* values rather than computing
with them.  Selection makes the bit-exactness contract provable: the
chosen order statistics are bitwise the values ``jnp.sort`` would place at
those positions (stable ranks are a permutation; ties share one value).
The kernel therefore returns ``(a, b, n)`` — the two selected statistics
and the valid count — and the *wrapper*
(:func:`repro.kernels.ops.gate_threshold`) applies ``np.quantile``'s lerp
in the identical expression shape :func:`quantile_threshold` uses, so
both lower to the same XLA elementwise graph (same fused-multiply-add
decisions) and kernel == jnp path bit-for-bit — the contract
``tests/test_kernels.py`` property-tests.  (Computing the lerp *inside*
the kernel came out one ulp off on some windows: the Pallas interpreter
and the jnp graph made different mul+add contraction choices.)

Layout: epochs run along the 128 lanes, window slots along the sublanes.
The trace is held lane-dense (``[rows, 128]``) and VMEM-resident; a grid
step takes one row of 128 epochs, loads that row and the next (the
windows reach past it), and builds its ``[W, 128]`` window block with one
strided lane rotation (``pltpu.roll``): sublane ``k`` is the two rows
rotated left by ``W - 1 - k``, so it holds slot ``w = W - 1 - k`` of
every epoch's window.  The rank count then compares whole vregs, the
selections reduce over sublanes, and the traced window length arrives
as a scalar in SMEM.  No gather, no sort, no 1-D dynamic slice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8


def _kernel(win_ref, int_ref, theta_ref, a_ref, b_ref, n_ref, *,
            n_epochs: int, max_window: int, w_rows: int, x_rows: int):
    """One block of 128 epochs -> (a, b, n), each [1, 128].

    win_ref: [1, 1] i32 in SMEM (the traced window length); int_ref: the
    whole trace, [rows, 128] f32; theta_ref: [1, 128] f32.  a/b are the
    ``floor(theta*(n-1))``-th and successor order statistics of each
    epoch's window; n is its valid count.
    """
    p = pl.program_id(0)
    window = win_ref[0, 0]
    rows = int_ref[pl.ds(p, x_rows), :]                 # [x_rows, 128]
    x = jnp.concatenate([rows[r:r + 1] for r in range(x_rows)], axis=1)
    span = x_rows * LANE
    # Sublane k <- x rotated left by w_rows-1-k: win[k, j] = trace[t + w],
    # slot w = w_rows-1-k of epoch t = 128p + j.
    win = pltpu.roll(jnp.broadcast_to(x, (w_rows, span)),
                     (span - (w_rows - 1)) % span, 1,
                     stride=1, stride_axis=0)[:, :LANE]
    k = jax.lax.broadcasted_iota(jnp.int32, (w_rows, LANE), 0)
    w = (w_rows - 1) - k
    t = p * LANE + jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    valid = (w < window) & (w < max_window) & (t + w < n_epochs)
    win = jnp.where(valid, win, jnp.inf)                # invalid slots last
    # The valid count, in closed form: #{w < max_window : w < window,
    # t + w < n_epochs}.
    n = jnp.maximum(jnp.minimum(jnp.minimum(window, max_window),
                                n_epochs - t), 0)       # [1, 128]

    # Selection indices — the exact index arithmetic of quantile_threshold
    # (vi is one multiply and floor is exact, so lo_i/hi_i are bitwise the
    # indices the jnp path gathers at; the *lerp* happens in the wrapper).
    vi = theta_ref[...] * (n - 1).astype(jnp.float32)
    lo_i = jnp.floor(vi).astype(jnp.int32)
    hi_i = jnp.minimum(lo_i + 1, n - 1)

    # Stable rank of every slot: valid slots get a permutation of 0..n-1
    # (ties broken by sublane), +inf slots rank >= n — never selected.
    rank = jnp.zeros((w_rows, LANE), jnp.int32)
    for u in range(w_rows):
        x_u = win[u:u + 1, :]
        rank += ((x_u < win) | ((x_u == win) & (u < k))).astype(jnp.int32)

    # Select the two order statistics (exactly one slot matches each rank;
    # summing the zeros is the identity, so the selection is exact).
    a_ref[...] = jnp.sum(jnp.where(rank == lo_i, win, 0.0), axis=0,
                         keepdims=True)
    b_ref[...] = jnp.sum(jnp.where(rank == hi_i, win, 0.0), axis=0,
                         keepdims=True)
    n_ref[...] = n


@functools.partial(jax.jit, static_argnames=("max_window", "interpret"))
def gate_quantile_stats_pallas(intensity: jnp.ndarray, theta: jnp.ndarray,
                               window: jnp.ndarray, *, max_window: int,
                               interpret: bool
                               ) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """intensity [E] f32; theta [E] f32 (per-epoch — broadcast a scalar
    upstream); window scalar/[1] i32 (traced; capped by ``max_window``,
    the static width, exactly like the jnp path's array width caps it).
    Returns ``(a, b, n)``, each [E]: the two order statistics
    ``np.quantile``'s lerp interpolates between (bitwise the values
    ``sorted_windows``' sort would place at those positions) and the valid
    window length.  The wrapper (:func:`repro.kernels.ops.gate_threshold`)
    finishes the lerp in :func:`quantile_threshold`'s exact expression.

    ``interpret`` is **required**: callers go through
    :mod:`repro.kernels.ops`, where the backend-aware default lives.

    Epochs past the horizon (block padding) select from all-invalid
    windows; they are sliced off before returning.
    """
    E = intensity.shape[0]
    Rp = -(-E // LANE)                       # epoch rows, one per grid step
    w_rows = -(-max_window // SUBLANE) * SUBLANE
    x_rows = 1 + -(-(w_rows - 1) // LANE)    # trace rows one block reads
    Rt = -(-(Rp + x_rows - 1) // SUBLANE) * SUBLANE

    intp = jnp.pad(intensity.astype(jnp.float32),
                   (0, Rt * LANE - E)).reshape(Rt, LANE)
    thetap = jnp.pad(theta.astype(jnp.float32),
                     (0, Rp * LANE - E)).reshape(Rp, 1, LANE)
    win1 = jnp.reshape(window.astype(jnp.int32), (1, 1))

    kernel = functools.partial(_kernel, n_epochs=E, max_window=max_window,
                               w_rows=w_rows, x_rows=x_rows)
    row_spec = pl.BlockSpec((None, 1, LANE), lambda p: (p, 0, 0))
    a, b, n = pl.pallas_call(
        kernel,
        grid=(Rp,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda p: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((Rt, LANE), lambda p: (0, 0)),
            row_spec,
        ],
        out_specs=[row_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((Rp, 1, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1, LANE), jnp.float32),
                   jax.ShapeDtypeStruct((Rp, 1, LANE), jnp.int32)],
        interpret=interpret,
    )(win1, intp, thetap)
    return tuple(o.reshape(Rp * LANE)[:E] for o in (a, b, n))
