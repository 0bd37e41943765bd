"""Activation sharding hints (sequence / context parallelism).

``hint(x, *axes)`` applies ``with_sharding_constraint`` when called under a
mesh (``jax.sharding.set_mesh``) whose axis names include the requested
ones, and is a no-op otherwise (CPU tests, single-device runs).  This is how the DSE's chosen activation
folding materialises without threading mesh objects through model code.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _mesh_axes():
    """Axis names of the mesh set by ``jax.sharding.set_mesh`` (empty when
    none is set)."""
    return set(jax.sharding.get_abstract_mesh().axis_names)


def hint(x, spec: P):
    """Sharding constraint that drops axes the mesh doesn't have; a no-op
    without a mesh.  A constraint the mesh rejects raises."""
    axes = _mesh_axes()
    if not axes:
        return x
    fixed = []
    for ax in tuple(spec) + (None,) * (x.ndim - len(tuple(spec))):
        if ax is None:
            fixed.append(None)
        elif isinstance(ax, (tuple, list)):
            keep = tuple(a for a in ax if a in axes)
            fixed.append(keep if keep else None)
        else:
            fixed.append(ax if ax in axes else None)
    return jax.lax.with_sharding_constraint(x, P(*fixed[:x.ndim]))


def seq_shard_hint(x, enabled: bool):
    """Sequence parallelism: shard the T axis of (B, T, D) over 'model'."""
    if not enabled:
        return x
    return hint(x, P(None, "model", None))
