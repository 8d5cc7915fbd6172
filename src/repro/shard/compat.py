"""The single ``shard_map`` entry point.

Every ``shard_map`` call in the repo — the MoE expert-parallel path in
:mod:`repro.models.moe` and the instance-axis sharding layer in
:mod:`repro.shard` — routes through :func:`shard_map_compat`, so its
settings live in exactly one place.
"""
from __future__ import annotations

import jax


def shard_map_compat(body, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off.

    The callers' output collectives (MoE's psum, the instance layer's
    all_gather) make outputs fully replicated where the specs say so, but
    the checker can't prove it through scatters, so ``check_vma`` is off.
    """
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
