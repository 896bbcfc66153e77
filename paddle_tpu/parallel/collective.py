"""Functional collectives over named mesh axes.

Reference analog: operators/collective/ c_* ops + python/paddle/fluid/
layers/collective.py (_allreduce:20, _c_broadcast:93, _c_allgather:108,
_c_reducescatter:133). `ring_id` ↔ axis name; NCCL streams/sync ops vanish
(XLA orders by data dependence).

Two usage contexts:
- inside `shard_map` per-device code: these are thin lax wrappers;
- at the array level: `shard_map`-wrapped helpers below take a Mesh and
  return globally-transformed arrays.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import Mesh, PartitionSpec as P


def shard_map(f, mesh, in_specs, out_specs, check_vma=False,
              axis_names=None):
    """Thin wrapper; `axis_names` (a subset of mesh axes) makes only those
    axes manual — the rest stay under automatic GSPMD propagation inside the
    body. That is how manual schedules (the GPipe ppermute ring) compose
    with automatic dp/tp sharding in ONE program."""
    if axis_names is not None:
        return _shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=check_vma,
                          axis_names=frozenset(axis_names))
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_vma)


# -- per-device primitives (use inside shard_map) ---------------------------

def psum(x, axis: str):
    return lax.psum(x, axis)


def pmean(x, axis: str):
    return lax.pmean(x, axis)


def ppermute(x, axis: str, perm):
    return lax.ppermute(x, axis, perm)


def axis_index(axis: str):
    return lax.axis_index(axis)


# -- array-level collectives (build + run a shard_map) ----------------------

def all_reduce(x, mesh: Mesh, axis: str, op: str = "sum"):
    """c_allreduce_{sum,max,min} parity on an axis-sharded array."""
    fns = {"sum": lax.psum, "max": lax.pmax, "min": lax.pmin, "mean": lax.pmean}
    fn = fns[op]
    spec = P(axis)
    return shard_map(lambda v: fn(v, axis), mesh,
                     in_specs=(spec,), out_specs=spec)(x)


def all_gather(x, mesh: Mesh, axis: str, tiled: bool = True):
    """c_allgather parity: gather shards along leading dim."""
    return shard_map(lambda v: lax.all_gather(v, axis, tiled=tiled), mesh,
                     in_specs=(P(axis),), out_specs=P())(x)


def reduce_scatter(x, mesh: Mesh, axis: str):
    """c_reducescatter parity: x replicated → scattered sums."""
    return shard_map(lambda v: lax.psum_scatter(v, axis, tiled=True), mesh,
                     in_specs=(P(),), out_specs=P(axis))(x)


def broadcast(x, mesh: Mesh, axis: str, root: int = 0):
    """c_broadcast parity: root's shard replicated to all."""

    def f(v):
        idx = lax.axis_index(axis)
        src = jnp.where(idx == root, v, jnp.zeros_like(v))
        return lax.psum(src, axis)

    return shard_map(f, mesh, in_specs=(P(axis),), out_specs=P())(x)


def all_to_all(x, mesh: Mesh, axis: str, split_axis: int, concat_axis: int):
    """Ulysses-style head/sequence exchange (no reference analog — new
    capability for sequence parallelism)."""

    def f(v):
        return lax.all_to_all(v, axis, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)

    return shard_map(f, mesh, in_specs=(P(axis),), out_specs=P(axis))(x)


def barrier(mesh: Mesh, axis: Optional[str] = None):
    """fetch_barrier/send_barrier analog: a psum forces a sync point."""
    axes = [axis] if axis else list(mesh.axis_names)
    x = jnp.zeros(())
    for a in axes:
        x = shard_map(lambda v: lax.psum(v, a), mesh,
                      in_specs=(P(),), out_specs=P())(x)
    return jax.block_until_ready(x)
