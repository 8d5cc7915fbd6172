"""Mixture-of-Experts FFN with shard_map expert parallelism.

Dispatch is sort-free scatter-to-capacity (MaxText-style "dropping" MoE):
each device holds ``E_loc = E / model`` experts and the *full* token set of
its data shard (activations are replicated over the tensor axis, the
standard TP region invariant).  Every device therefore dispatches locally —
no all-to-all — computes its experts' FFN on a ``[E_loc, C, D]`` capacity
buffer, scatters results back to token order, and a single ``psum`` over
``"model"`` combines the k expert contributions (the same all-reduce a
dense TP MLP needs, so MoE costs one collective, not three).

With ``zero_stage >= 3`` the expert weights additionally arrive sharded on
their ``D`` dim over the data axes and are all-gathered on entry (explicit
ZeRO-3; the gather bytes show up in the roofline collective term).

``moe_ref`` is the exact dense oracle (every expert on every token) used by
tests; with a capacity factor large enough to avoid drops the EP path must
match it to bf16 tolerance.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ArchConfig
from repro.models.layers import activation, cast
from repro.models.params import ParamDef
from repro.models.parallel import ParallelCfg
# The single shard_map entry point lives with the
# instance-axis sharding layer; the EP psum makes this body's output fully
# replicated, which its disabled checker can't prove (see there).
from repro.shard.compat import shard_map_compat as _shard_map


def moe_defs(cfg: ArchConfig) -> dict:
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    glu = 2 if cfg.act.endswith("_glu") else 1
    defs = {
        "router": ParamDef((D, E), ("embed", None), init="scaled"),
        # Expert weights carry their own logical name for the d_model dim
        # ("expert_embed") so ZeRO can shard the expert bank over data
        # without touching the dense layers (zero_stage=2, the kimi mode).
        "w_in": ParamDef((E, D, glu, F),
                         ("expert", "expert_embed", None, "expert_mlp"),
                         init="scaled"),
        "w_out": ParamDef((E, F, D), ("expert", "expert_mlp",
                                      "expert_embed"), init="scaled"),
    }
    if cfg.n_shared_experts:
        S = cfg.n_shared_experts
        defs["shared_in"] = ParamDef((D, glu, S * F), ("embed", None, "mlp"),
                                     init="scaled")
        defs["shared_out"] = ParamDef((S * F, D), ("mlp", "embed"),
                                      init="scaled")
    return defs


def _capacity(n_tokens: int, k: int, n_experts: int, factor: float) -> int:
    c = int(math.ceil(factor * k * n_tokens / n_experts))
    return max(4, -(-c // 4) * 4)


def _route(x2d: jnp.ndarray, router: jnp.ndarray, k: int):
    """x2d [N, D] -> (ids [N,k] int32, weights [N,k] f32, probs [N,E] f32)."""
    logits = jnp.einsum("nd,de->ne", x2d, cast(router),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return ids.astype(jnp.int32), w, probs


def _expert_ffn(buf: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                act: str) -> jnp.ndarray:
    """buf [E, C, D] -> [E, C, D] through each expert's FFN."""
    h = jnp.einsum("ecd,edgf->ecgf", buf, w_in,
                   preferred_element_type=jnp.float32)
    h = activation(h, act).astype(buf.dtype)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def _dispatch_compute(x2d, ids, wgt, w_in, w_out, *, e_first: jnp.ndarray,
                      e_local: int, capacity: int, act: str) -> jnp.ndarray:
    """Scatter tokens routed to experts [e_first, e_first+e_local) into a
    capacity buffer, run the FFNs, scatter back. Returns [N, D] (partial —
    only this device's experts' contributions)."""
    N, D = x2d.shape
    k = ids.shape[1]
    flat_e = ids.reshape(-1) - e_first                       # [N*k]
    tok = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    in_range = (flat_e >= 0) & (flat_e < e_local)
    le = jnp.where(in_range, flat_e, e_local)                # drop bucket
    # Rank of each slot within its expert (exclusive running count).
    onehot = jax.nn.one_hot(le, e_local + 1, dtype=jnp.int32)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)[jnp.arange(le.shape[0]), le]
    keep = in_range & (rank < capacity)
    dest = jnp.where(keep, le * capacity + rank, e_local * capacity)
    buf = jnp.zeros((e_local * capacity + 1, D), x2d.dtype)
    buf = buf.at[dest].add(jnp.where(keep[:, None], x2d[tok], 0))
    out_buf = _expert_ffn(buf[:-1].reshape(e_local, capacity, D),
                          w_in, w_out, act)
    y_slot = out_buf.reshape(e_local * capacity, D)[
        jnp.minimum(dest, e_local * capacity - 1)]
    y_slot = jnp.where(keep[:, None], y_slot, 0) * wgt.reshape(-1)[:, None]
    y = jnp.zeros_like(x2d).at[tok].add(y_slot.astype(x2d.dtype))
    return y


def aux_loss(probs: jnp.ndarray, ids: jnp.ndarray, n_experts: int
             ) -> jnp.ndarray:
    """Switch-style load-balancing loss: E * <f_e, p_e>."""
    pe = probs.reshape(-1, n_experts).mean(0)
    fe = jnp.zeros(n_experts).at[ids.reshape(-1)].add(1.0)
    fe = fe / jnp.maximum(fe.sum(), 1.0)
    return n_experts * jnp.sum(pe * fe)


def moe_apply(p: dict, x: jnp.ndarray, cfg: ArchConfig, par: ParallelCfg
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] -> (y [B, S, D], aux_loss scalar)."""
    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.experts_per_token
    x2d = x.reshape(-1, D)
    ids, wgt, probs = _route(x2d, p["router"], k)
    aux = aux_loss(probs, ids, E)

    msize = par.model_axis_size
    if par.mesh is None or not par.moe_ep or msize == 1:
        cap = _capacity(x2d.shape[0], k, E, cfg.capacity_factor)
        y = _dispatch_compute(
            x2d, ids, wgt, cast(p["w_in"]), cast(p["w_out"]),
            e_first=jnp.int32(0), e_local=E, capacity=cap, act=cfg.act)
    else:
        y = _moe_ep(x2d, ids, wgt, p["w_in"], p["w_out"], cfg, par)
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        h = jnp.einsum("bsd,dgf->bsgf", x, cast(p["shared_in"]))
        h = activation(h, cfg.act).astype(x.dtype)
        y = y + jnp.einsum("bsf,fd->bsd", h, cast(p["shared_out"]))
    return y, aux


def _moe_ep(x2d, ids, wgt, w_in, w_out, cfg: ArchConfig, par: ParallelCfg):
    """shard_map expert-parallel path (see module docstring)."""
    mesh = par.mesh
    E, k = cfg.n_experts, cfg.experts_per_token
    e_local = E // par.model_axis_size
    rules = par.effective_rules()
    fsdp = rules.mesh_axes("expert_embed")   # None unless zero_stage >= 2
    bt = par.batch_axes or None
    tok_spec = P(bt, None)
    w_in_spec = P("model", fsdp, None, None)
    w_out_spec = P("model", None, fsdp)

    n_shard = x2d.shape[0] // math.prod(
        mesh.shape[a] for a in (par.batch_axes or ()))
    cap = _capacity(n_shard, k, E, cfg.capacity_factor)

    def body(x_loc, ids_loc, wgt_loc, w_in_loc, w_out_loc):
        # Cast BEFORE the ZeRO-3 gather: the all-gather then moves bf16,
        # not fp32 — half the wire bytes (§Perf, kimi iteration 1).
        w_in_loc, w_out_loc = cast(w_in_loc), cast(w_out_loc)
        if fsdp is not None:
            w_in_loc = jax.lax.all_gather(w_in_loc, fsdp, axis=1, tiled=True)
            w_out_loc = jax.lax.all_gather(w_out_loc, fsdp, axis=2,
                                           tiled=True)
        e_first = jax.lax.axis_index("model") * e_local
        y = _dispatch_compute(
            x_loc, ids_loc, wgt_loc, w_in_loc, w_out_loc,
            e_first=e_first, e_local=e_local, capacity=cap, act=cfg.act)
        return jax.lax.psum(y, "model")

    fn = _shard_map(
        body, mesh=mesh,
        in_specs=(tok_spec, tok_spec, tok_spec, w_in_spec, w_out_spec),
        out_specs=tok_spec)
    return fn(x2d, ids, wgt, w_in, w_out)


def moe_ref(p: dict, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Dense oracle: every expert on every token, exact top-k combine."""
    B, S, D = x.shape
    x2d = x.reshape(-1, D)
    ids, wgt, _ = _route(x2d, p["router"], cfg.experts_per_token)
    h = jnp.einsum("nd,edgf->negf", x2d, cast(p["w_in"]))
    h = activation(h, cfg.act).astype(x2d.dtype)
    y_all = jnp.einsum("nef,efd->ned", h, cast(p["w_out"]))  # [N, E, D]
    sel = jnp.take_along_axis(y_all, ids[..., None], axis=1)  # [N, k, D]
    y = (sel * wgt[..., None].astype(sel.dtype)).sum(1)
    if cfg.n_shared_experts:
        hs = jnp.einsum("nd,dgf->ngf", x2d, cast(p["shared_in"]))
        hs = activation(hs, cfg.act).astype(x2d.dtype)
        y = y + jnp.einsum("nf,fd->nd", hs, cast(p["shared_out"]))
    return y.reshape(B, S, D)
