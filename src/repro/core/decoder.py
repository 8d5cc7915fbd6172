"""Schedule-generation-scheme (SGS) decoders in JAX.

The paper solves the FJSP with CP-SAT.  On a TPU we instead search over a
*decodable encoding*: a candidate is a priority vector ``prio[T]`` (which
task to place next) plus, optionally, an explicit machine assignment
``assign[T]``.  :func:`sgs` turns a candidate into a feasible schedule with a
``lax.scan`` over tasks; :func:`timing_sweep` then shifts tasks later inside
their slack windows to chase low-carbon periods (the carbon-greedy timing
pass).  Both are shape-static and vmap over populations and batched
instances — that data-parallel search is the TPU-native replacement for the
paper's sequential CP solver (DESIGN.md §3).

Feasibility invariants (property-tested against the shared validator,
:mod:`repro.core.validate`): every decoded schedule respects arrivals
(Eq. 4), DAG precedence (Eq. 5), machine validity (Eq. 6) and per-machine
no-overlap (Eq. 8) — by construction; :func:`timing_sweep` additionally
never exceeds its deadline and never increases carbon.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.instance import PackedInstance
from repro.obs.scopes import scope

BIG = jnp.int32(1 << 28)

MACHINE_RULES = ("fixed", "earliest_finish", "min_energy")


class DecodedSchedule(NamedTuple):
    start: jnp.ndarray    # int32 [T]
    assign: jnp.ndarray   # int32 [T]
    seq_key: jnp.ndarray  # int32 [T] placement order (for timing sweeps)


@functools.partial(jax.jit, static_argnames=("machine_rule",))
def sgs(inst: PackedInstance, prio: jnp.ndarray,
        assign: jnp.ndarray | None = None,
        machine_rule: str = "earliest_finish") -> DecodedSchedule:
    """Serial SGS: place the highest-priority *ready* task at its earliest
    feasible start, T times.

    machine_rule:
      * ``"fixed"``            — use ``assign`` verbatim (it must be allowed).
      * ``"earliest_finish"``  — greedy: machine minimizing completion time.
      * ``"min_energy"``       — greedy: machine minimizing P_m * p_{t,m},
                                  finish time as tie-break.

    For any feasible schedule S there is a priority order (S's start order)
    under which earliest-start SGS with S's assignment starts every task no
    later than S does — so the encoding's image contains a makespan-optimal
    schedule (see DESIGN.md §3).
    """
    if machine_rule not in MACHINE_RULES:
        raise ValueError(f"unknown machine_rule {machine_rule!r}")
    with scope("sgs"):
        T, M = inst.T, inst.M
        real = inst.task_mask
        pred_real = inst.pred & real[None, :]
        if assign is None:
            assign = jnp.zeros((T,), jnp.int32)

        tvec = jnp.arange(T, dtype=jnp.int32)
        mvec = jnp.arange(M, dtype=jnp.int32)

        # Every read and write of task t's or machine m's entry is a select
        # on a one-hot mask: under vmap an index by a per-candidate t or m
        # becomes a gather or scatter, which a TPU runs element by element.
        # Each reduction has one nonzero term, so the bits are the index's.
        def body(state, i):
            scheduled, comp, mfree, start, aout, seq = state
            pending = jnp.any(pred_real & ~scheduled[None, :], axis=1)
            ready = ~scheduled & ~pending
            t = jnp.argmax(jnp.where(ready, prio, -jnp.inf))
            oh = tvec == t
            pred_t = jnp.any(oh[:, None] & pred_real, axis=0)
            pred_comp = jnp.max(jnp.where(pred_t, comp, 0))
            base = jnp.maximum(jnp.sum(jnp.where(oh, inst.arrival, 0)),
                               pred_comp)
            est_m = jnp.maximum(base, mfree)               # [M]
            dur_t = jnp.sum(jnp.where(oh[:, None], inst.dur, 0), axis=0)
            fin_m = est_m + dur_t
            ok = jnp.any(oh[:, None] & inst.allowed, axis=0)
            if machine_rule == "fixed":
                m = jnp.sum(jnp.where(oh, assign, 0)).astype(jnp.int32)
            elif machine_rule == "earliest_finish":
                m = jnp.argmin(jnp.where(ok, fin_m, BIG)).astype(jnp.int32)
            else:  # min_energy
                cost = inst.power * dur_t.astype(jnp.float32)
                key = jnp.where(ok, cost * 65536.0 + fin_m.astype(jnp.float32),
                                jnp.float32(3e38))
                m = jnp.argmin(key).astype(jnp.int32)
            ohm = mvec == m
            s = jnp.sum(jnp.where(ohm, est_m, 0))
            c = s + jnp.sum(jnp.where(ohm, dur_t, 0))
            return (scheduled | oh,
                    jnp.where(oh, c, comp),
                    jnp.where(ohm, jnp.maximum(mfree, c), mfree),
                    jnp.where(oh, s, start),
                    jnp.where(oh, m, aout),
                    jnp.where(oh, i, seq)), None

        init = (jnp.zeros((T,), bool), jnp.zeros((T,), jnp.int32),
                jnp.zeros((M,), jnp.int32), jnp.zeros((T,), jnp.int32),
                jnp.zeros((T,), jnp.int32), jnp.zeros((T,), jnp.int32))
        (_, _, _, start, aout, seq), _ = jax.lax.scan(body, init, tvec)
        return DecodedSchedule(start, aout, seq)


def sweep_table(inst: PackedInstance,
                cum: jnp.ndarray) -> jnp.ndarray | None:
    """Every start-cost row :func:`timing_sweep` can ask for, in three
    bf16 parts whose f32 sum is the row exactly — or ``None``, where the
    sweep gathers each row itself.

    Row ``t*M + m`` is ``cum[min(s + dur[t, m], H)] - cum[s]`` for
    ``s = 0..H``: the emissions of task ``t`` on machine ``m`` started at
    ``s``, per unit power.  The sweep gathers that row per step and
    candidate, which a TPU runs element by element: the bound's time then
    grows with instances x population x horizon.  With a table the sweep
    picks the row with a one-hot product instead (:func:`_select_row`),
    which runs on the MXU.  Off a TPU the gather is the cheap form, and
    no table is built.

    The table is ``[3, T*M, H+1]``: each row's high, middle and low
    parts, eight significand bits each, whose f32 sum is the row, so both
    forms give the same row bit for bit.  Two properties of the chip's
    compiler shape it (found on the paper's 10-server batch, 400 rows, on
    a TPU v5e).  Each part is cut by :func:`_top_bits` and never rounded
    through bf16: the compiler may keep such a round trip in f32, which
    empties the remainder ``x - bf16(x)``.  And the three parts live in
    one array: held as three at 400 rows, the bound's fused row selection
    and argmin stepped over 4 instances at a time, and a v5e returned
    argmin 0 in every window but the last of each 8-instance tile
    (``tests/test_tpu_compile.py`` checks both properties).
    """
    if jax.default_backend() != "tpu":
        return None
    if cum.dtype != jnp.float32:
        raise TypeError(f"sweep_table splits f32 rows exactly; cum is "
                        f"{cum.dtype}")
    with scope("sweep_table"):
        T, M = inst.T, inst.M
        H = cum.shape[0] - 1
        svec = jnp.arange(H + 1, dtype=jnp.int32)
        end = jnp.minimum(svec + inst.dur[:, :, None], H)        # [T, M, H+1]
        rows = (cum[end] - cum[svec]).reshape(T * M, H + 1)
        high = _top_bits(rows)
        rest = rows - high
        mid = _top_bits(rest)
        return jnp.stack([high, mid, rest - mid]).astype(jnp.bfloat16)


def _top_bits(x: jnp.ndarray) -> jnp.ndarray:
    """``x`` (f32) with the low 16 bits of its pattern cleared: its sign,
    exponent and top eight significand bits, a bf16 value held in f32.
    ``x - _top_bits(x)`` is exact and has at most 16 significand bits."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _select_row(table: jnp.ndarray, j: jnp.ndarray) -> jnp.ndarray:
    """Row ``j`` of a :func:`sweep_table`, bit-exact: each one-hot product
    returns one bf16 part exactly in f32, and the partial sums of the
    parts are exact."""
    onehot = (jnp.arange(table.shape[1]) == j).astype(jnp.bfloat16)
    b1, b2, b3 = (jnp.dot(onehot, b, preferred_element_type=jnp.float32)
                  for b in table)
    return (b1 + b2) + b3


@functools.partial(jax.jit, static_argnames=("sweeps",))
def timing_sweep(inst: PackedInstance, start: jnp.ndarray,
                 assign: jnp.ndarray, cum: jnp.ndarray,
                 deadline: jnp.ndarray, sweeps: int = 2,
                 frozen: jnp.ndarray | None = None,
                 table: jnp.ndarray | None = None) -> jnp.ndarray:
    """Carbon-greedy timing pass.

    Keeps sequencing (per-machine order and DAG order) fixed and pushes each
    task *later* into its slack window to the start minimizing its own
    emissions ``cum[s+d] - cum[s]``, never exceeding ``deadline``.  Processing
    tasks in descending start order makes each task's successors (DAG and
    machine) final before the task itself is placed, so a sweep preserves
    feasibility; extra sweeps exploit slack opened by earlier sweeps.

    ``frozen`` (optional bool [T]) pins tasks in place: a frozen task is
    never moved, but still constrains its neighbours — the rolling replanner
    (:mod:`repro.core.solvers.rolling`) freezes tasks that have already
    started executing, which cannot be shifted retroactively.

    With fixed sequences this is coordinate descent on the separable
    start-time-cost problem — cheap, monotone (never increases carbon), and
    exact in the common case of a task whose window covers a clean valley.

    ``table`` is :func:`sweep_table` of ``(inst, cum)``, built here when
    not given; a solver that sweeps many candidates of one instance builds
    it once and passes it (XLA leaves it inside the search loop).
    """
    with scope("timing_sweep"):
        T, M = inst.T, inst.M
        H = cum.shape[0] - 1
        real = inst.task_mask
        sweepable = real if frozen is None else real & ~frozen
        tvec = jnp.arange(T, dtype=jnp.int32)
        mvec = jnp.arange(M, dtype=jnp.int32)
        svec = jnp.arange(H + 1, dtype=jnp.int32)
        # As in :func:`sgs`, every read and write of a task's or machine's
        # entry is a select on a one-hot mask, never an index by the
        # candidate's own t or assignment: under vmap that is a gather or
        # scatter, which a TPU runs element by element.  Each reduction has
        # one nonzero term, so the bits are the index's.  Task t's machine
        # successors are read from ``assign``, with no [T, T] array.
        d = jnp.sum(jnp.where(assign[:, None] == mvec, inst.dur, 0), axis=1)
        if table is None:
            table = sweep_table(inst, cum)
        succ = inst.pred.T & real[None, :]          # succ[t, v]: t -> v edge

        def one_sweep(start):
            # Freeze the sequence key for this sweep: (start, idx) descending.
            key = start * jnp.int32(T) + tvec
            order = jnp.argsort(-jnp.where(real, key, -BIG))  # pads last

            def body(start_cur, t):
                oh = tvec == t
                dt = jnp.sum(jnp.where(oh, d, 0))
                a_t = jnp.sum(jnp.where(oh, assign, 0))
                lo = jnp.sum(jnp.where(oh, start_cur, 0))
                succ_t = jnp.any(oh[:, None] & succ, axis=0)
                succ_cap = jnp.min(jnp.where(succ_t, start_cur, BIG))
                after = ((assign == a_t) & real
                         & (key > jnp.sum(jnp.where(oh, key, 0))))
                mnext_cap = jnp.min(jnp.where(after, start_cur, BIG))
                hi = jnp.minimum(jnp.minimum(succ_cap, mnext_cap),
                                 deadline.astype(jnp.int32)) - dt
                if table is None:
                    cost = cum[jnp.minimum(svec + dt, H)] - cum[svec]
                else:
                    cost = _select_row(table, t * M + a_t)
                cost = jnp.where((svec >= lo) & (svec <= hi), cost, jnp.inf)
                # The barrier keeps the argmin out of the fusion of the row
                # product and the mask: a v5e returned argmin 0 from such a
                # fusion stepped over fewer than 8 instances (sweep_table).
                # Alone, the argmin steps whole tiles.
                cost = jax.lax.optimization_barrier(cost)
                s_star = jnp.argmin(cost).astype(jnp.int32)
                movable = jnp.any(oh & sweepable) & (hi >= lo)
                return jnp.where(oh & movable, s_star, start_cur), None

            start, _ = jax.lax.scan(body, start, order)
            return start

        for _ in range(sweeps):
            start = one_sweep(start)
        return start


@jax.jit
def upward_rank(inst: PackedInstance) -> jnp.ndarray:
    """HEFT-style upward rank: mean duration + longest path to a sink.

    Used as the priority initialization (critical-path-first); candidates add
    noise around it.  Tasks are topologically indexed, so a reverse
    ``fori_loop`` suffices.
    """
    T = inst.T
    mdur = jnp.where(inst.allowed, inst.dur, 0).sum(1) / \
        jnp.maximum(inst.allowed.sum(1), 1)
    succ = inst.pred.T & inst.task_mask[None, :]   # succ[t, v]

    def body(i, rank):
        t = T - 1 - i
        best_succ = jnp.max(jnp.where(succ[t], rank, 0.0))
        return rank.at[t].set(mdur[t] + best_succ)

    rank = jax.lax.fori_loop(0, T, body, jnp.zeros((T,), jnp.float32))
    return jnp.where(inst.task_mask, rank, -1e9)
