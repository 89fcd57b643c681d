"""Small shard_map helpers shared by the manual-collective modules
(ring attention, pipeline, MoE, DP grad sync)."""
from __future__ import annotations

import jax
from jax import lax


def pvary(xs, axes):
    """Mark values as varying over the given manual mesh axes (shard_map's
    vma type system)."""
    axes = tuple(axes)
    if not axes:
        return xs
    return lax.pcast(xs, axes, to="varying")


def shard_map(f, mesh, in_specs, out_specs, axis_names=None):
    """``jax.shard_map`` with ``axis_names`` given as any iterable (the
    manual axes; the rest are left to GSPMD)."""
    kw = {}
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
