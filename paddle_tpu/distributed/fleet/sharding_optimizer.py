"""Sharding (ZeRO) optimizer stages — GSPMD mechanism.

Capability analog of ``python/paddle/distributed/fleet/meta_optimizers/
dygraph_optimizer/dygraph_sharding_optimizer.py:49`` (stage 1) and
``group_sharded_stage2/3`` (SURVEY D16). The reference partitions the
parameter list rank-by-rank and hand-codes reduce-scatter + broadcast; on
TPU the same memory win comes from *sharding annotations*: optimizer
moments (stage 1), gradients (stage 2), and parameters (stage 3/FSDP) are
pinned sharded along the ``sharding`` mesh axis, and XLA emits the
reduce-scatter/all-gather pairs inside the compiled step — the
"weight-update sharding" transform that is the published GSPMD recipe for
ZeRO on TPU.

Stage semantics:
- stage 1: accumulators sharded over the sharding axis (on the first
  free divisible dim, COMPOSED with any sharding the state already
  carries — a pipeline-stacked weight keeps its pp dim, TP weights
  their mp dim). Under jit capture the sharding is applied as
  ``with_sharding_constraint`` inside the compiled step.
- stage 2: + gradients resharded before the update.
- stage 3: + parameters stored sharded; all-gather happens inside forward
  (XLA inserts it where the full weight is consumed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.tensor import Tensor
from .topology import HybridCommunicateGroup


def _spec_names(spec):
    names = set()
    for s in spec:
        if s is None:
            continue
        names.update(s if isinstance(s, (tuple, list)) else (s,))
    return names


def _compose_parts(shape, cur, own_mesh, fallback_mesh, axis_name):
    """Core of the compose: given an existing partial spec ``cur`` over
    ``own_mesh``, pick the first free divisible dim for ``axis_name``.
    None = leave as is (a 0-d accumulator always: one number has no dim
    to cut and stays replicated, whatever its parameter's spec)."""
    if not shape:
        return None
    cur = tuple(cur) + (None,) * (len(shape) - len(cur))
    names = _spec_names(cur)
    if axis_name in names:
        return None                       # already ZeRO-sharded
    if names:
        mesh = (own_mesh if own_mesh is not None
                and axis_name in getattr(own_mesh, "axis_names", ())
                else fallback_mesh)
        if (axis_name not in mesh.axis_names
                or not names <= set(mesh.axis_names)):
            return None                   # cannot express the compose
    else:
        mesh = fallback_mesh
        if axis_name not in mesh.axis_names:
            return None
    size = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    if size <= 1:
        return None
    for d in range(len(shape)):
        if cur[d] is None and shape[d] % size == 0 and shape[d] >= size:
            new = list(cur)
            new[d] = axis_name
            return mesh, P(*new)
    return None


def _compose_target(v, fallback_mesh, axis_name):
    """(mesh, spec) pinning ``v`` Shard over ``axis_name`` COMPOSED with
    any sharding it already carries (a pipeline-stacked weight is
    Shard('pp') on dim 0 and TP-sharded elsewhere — ZeRO must take a
    remaining dim, not fight those axes). None = leave as is."""
    sh = getattr(v, "sharding", None)
    return _compose_parts(v.shape, getattr(sh, "spec", None) or (),
                          getattr(sh, "mesh", None), fallback_mesh,
                          axis_name)


def _param_spec_parts(p):
    """(spec, mesh) annotated on a parameter — readable even when its
    value is a tracer (jit capture) via the ``_dist`` annotation."""
    dist = getattr(p, "_dist", None) if p is not None else None
    if not dist:
        return (), None
    mesh, placements = dist
    try:
        from ..auto_parallel.api import (ProcessMesh, _to_partition_spec)
        jmesh = mesh.jmesh if isinstance(mesh, ProcessMesh) else mesh
        if isinstance(placements, P):
            return tuple(placements), jmesh
        spec = _to_partition_spec(mesh, placements)
        return tuple(spec), jmesh
    except Exception:
        return (), None


class DygraphShardingOptimizer:
    """Wraps an inner optimizer; shards its state over the sharding axis."""

    def __init__(self, optimizer, hcg: HybridCommunicateGroup = None,
                 stage: int = 1):
        self._inner = optimizer
        # ZeRO shards per-param state over the sharding axis via GSPMD
        # constraint propagation; the fused flat-bucket path would fold
        # the moments into one unsharded buffer and defeat the sharding
        # — pin the inner optimizer to the per-param path
        if hasattr(optimizer, "_fused_off"):
            optimizer._fused_off = True
        if hcg is None:
            from .fleet import get_hybrid_communicate_group, init
            hcg = get_hybrid_communicate_group() or init()
        self._hcg = hcg
        self._mesh = hcg.mesh
        self._axis = "sharding"
        self._n = hcg.get_sharding_parallel_world_size()
        self.stage = stage

    # reference API: the inner optimizer's interface is preserved
    @property
    def _parameter_list(self):
        return getattr(self._inner, "_parameters", [])

    def _reshard_grads(self):
        for p in self._parameter_list:
            g = p.grad
            if g is None:
                continue
            v = g._read()
            if isinstance(v, jax.core.Tracer):
                cur, own = _param_spec_parts(p)
                tgt = _compose_parts(v.shape, cur, own, self._mesh,
                                     self._axis)
                if tgt is not None:
                    mesh, spec = tgt
                    g._write(jax.lax.with_sharding_constraint(
                        v, NamedSharding(mesh, spec)))
                continue
            tgt = _compose_target(v, self._mesh, self._axis)
            if tgt is not None:
                mesh, spec = tgt
                g._write(jax.device_put(v, NamedSharding(mesh, spec)))

    def _shard_accumulators(self):
        for _pid, acc in self._state_items():
            v = acc._read()
            if isinstance(v, jax.core.Tracer) or acc.is_dist():
                continue
            tgt = _compose_target(v, self._mesh, self._axis)
            if tgt is not None:
                mesh, spec = tgt
                acc._write(jax.device_put(
                    v, NamedSharding(mesh, spec)))
                acc._dist = (mesh, spec)

    def _state_items(self):
        items = []
        for store in self._inner._accumulators.values():
            items.extend(store.items())
        items.extend(getattr(self._inner, "_master_weights", {}).items())
        return items

    def _constrain_state_in_trace(self):
        """Under jit capture the accumulators / master weights hold
        tracers: apply ZeRO as ``with_sharding_constraint`` so the
        sharding lives INSIDE the compiled step (the GSPMD
        weight-update-sharding recipe). The compose base comes from the
        owning parameter's ``_dist`` annotation (a tracer carries no
        sharding to read)."""
        by_id = {id(p): p for p in self._parameter_list}
        for pid, acc in self._state_items():
            v = acc._read()
            if not isinstance(v, jax.core.Tracer):
                continue
            cur, own = _param_spec_parts(by_id.get(pid))
            tgt = _compose_parts(v.shape, cur, own, self._mesh,
                                 self._axis)
            if tgt is not None:
                mesh, spec = tgt
                acc._write(jax.lax.with_sharding_constraint(
                    v, NamedSharding(mesh, spec)))

    def step(self):
        if self._n > 1 and self.stage >= 2:
            self._reshard_grads()
        self._inner.step()
        if self._n > 1:
            # discovery/eager values are real (device_put path); the
            # replay and AOT traces see tracers (constraint path) —
            # each helper skips the other's case
            self._constrain_state_in_trace()
            self._shard_accumulators()

    def minimize(self, loss, *a, **k):
        if self._n > 1 and self.stage >= 2:
            self._reshard_grads()
        out = self._inner.minimize(loss, *a, **k)
        if self._n > 1:
            self._shard_accumulators()
        return out

    def clear_grad(self, *a, **k):
        return self._inner.clear_grad(*a, **k)

    def state_dict(self):
        return self._inner.state_dict()

    def set_state_dict(self, sd):
        return self._inner.set_state_dict(sd)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def group_sharded_parallel(model, optimizer, level="os_g", scaler=None,
                           group=None, **kwargs):
    """Reference ``python/paddle/distributed/sharding/group_sharded.py``:
    level 'os' = stage 1, 'os_g' = stage 2, 'p_g_os' = stage 3. Stage 3
    additionally pins the parameters themselves sharded (FSDP layout)."""
    stage = {"os": 1, "os_g": 2, "p_g_os": 3}[level]
    from .fleet import get_hybrid_communicate_group, init
    hcg = get_hybrid_communicate_group() or init()
    opt = DygraphShardingOptimizer(optimizer, hcg, stage=stage)
    if stage >= 3:
        for p in model.parameters():
            v = p._read()
            if isinstance(v, jax.core.Tracer) or p.is_dist():
                continue
            tgt = _compose_target(v, hcg.mesh, "sharding")
            if tgt is not None:
                mesh, spec = tgt
                p._write(jax.device_put(v, NamedSharding(mesh, spec)))
                p._dist = (mesh, spec)
    return model, opt, scaler
