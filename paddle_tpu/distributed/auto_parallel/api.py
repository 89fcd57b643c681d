"""Semi-automatic parallelism: the GSPMD path.

Capability analog of the reference semi-auto API (SURVEY D6/D7/D20;
``python/paddle/distributed/auto_parallel/api.py:126`` shard_tensor, ``:304``
reshard, ``:403`` shard_layer, ``:960`` shard_optimizer; DistTensor
``paddle/phi/core/distributed/auto_parallel/dist_tensor.h:39``; SPMD rules
``paddle/phi/infermeta/spmd_rules/``). TPU-native mechanism: the reference
implements SPMD propagation + an explicit reshard engine (pairwise
``{r,s,p}_to_{r,s,p}`` conversions) in C++; on TPU that whole machinery IS
XLA's GSPMD partitioner. ``shard_tensor`` pins a ``jax.sharding.
NamedSharding``; every op — eager (per-op jit) or captured by
``jit.to_static`` — propagates shardings through XLA's SPMD pass, which
also decides and inserts the collectives the reference's reshard functions
hand-code. ``Partial`` placements are metadata here: a single-controller
global-view array always holds summed values; unsummed partials exist only
inside compiled programs where XLA places the ``psum``.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...core.dispatch import apply
from ...core.tensor import Tensor, Parameter
from ...nn.layer import Layer


# --- placements (reference placement_types.h vocabulary) -------------------

class Placement:
    def is_shard(self, dim=None):
        return False

    def is_replicate(self):
        return False

    def is_partial(self):
        return False


class Shard(Placement):
    def __init__(self, dim: int):
        self.dim = dim

    def is_shard(self, dim=None):
        return dim is None or dim == self.dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("S", self.dim))


class Replicate(Placement):
    def is_replicate(self):
        return True

    def __repr__(self):
        return "Replicate()"

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("R")


class Partial(Placement):
    def __init__(self, reduce_type: str = "sum"):
        self.reduce_type = reduce_type

    def is_partial(self):
        return True

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, other):
        return isinstance(other, Partial) and \
            other.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("P", self.reduce_type))


# --- ProcessMesh -----------------------------------------------------------

class ProcessMesh:
    """Reference ``auto_parallel/process_mesh.py`` ProcessMesh: an N-D
    arrangement of device (process) ids with named dims. Wraps a
    ``jax.sharding.Mesh`` over the actual devices."""

    def __init__(self, mesh, dim_names: Optional[Sequence[str]] = None,
                 shape=None, process_ids=None):
        arr = np.asarray(mesh if mesh is not None else
                         np.asarray(process_ids).reshape(shape))
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        if len(dim_names) != arr.ndim:
            raise ValueError("dim_names must match mesh ndim")
        self._ids = arr
        self.dim_names = list(dim_names)
        devices = np.asarray(jax.devices(), dtype=object)
        dev_arr = np.empty(arr.shape, dtype=object)
        for idx in np.ndindex(arr.shape):
            dev_arr[idx] = devices[arr[idx]]
        self.jmesh = Mesh(dev_arr, tuple(self.dim_names))

    @property
    def shape(self):
        return list(self._ids.shape)

    @property
    def ndim(self):
        return self._ids.ndim

    @property
    def process_ids(self):
        return self._ids.flatten().tolist()

    @property
    def mesh(self):
        return self._ids

    def get_dim_size(self, name: str) -> int:
        return self._ids.shape[self.dim_names.index(name)]

    def get_rank_by_dim_and_process_id(self, dim, process_id):
        axis = self.dim_names.index(dim) if isinstance(dim, str) else dim
        loc = np.argwhere(self._ids == process_id)
        return int(loc[0][axis]) if len(loc) else -1

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh)
                and np.array_equal(self._ids, other._ids)
                and self.dim_names == other.dim_names)

    def __hash__(self):
        return hash((self._ids.tobytes(), tuple(self.dim_names)))

    def __repr__(self):
        return f"ProcessMesh({self._ids.tolist()}, {self.dim_names})"


_global_mesh: Optional[ProcessMesh] = None


def set_mesh(mesh: ProcessMesh):
    """Reference ``auto_parallel/api.py`` set_mesh / fleet.auto global mesh."""
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[ProcessMesh]:
    return _global_mesh


def _to_partition_spec(mesh, placements) -> P:
    """placements[i] describes mesh dim i (reference convention). Build the
    PartitionSpec over tensor dims; multiple mesh dims may shard one tensor
    dim (they compose in mesh-dim order). ``mesh`` may be a ProcessMesh or
    a raw jax Mesh."""
    dim_names = mesh.dim_names if isinstance(mesh, ProcessMesh) \
        else list(mesh.axis_names)
    by_tensor_dim: dict[int, list[str]] = {}
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            by_tensor_dim.setdefault(pl.dim, []).append(
                dim_names[mesh_dim])
    if not by_tensor_dim:
        return P()
    nspec = max(by_tensor_dim) + 1
    entries = []
    for d in range(nspec):
        names = by_tensor_dim.get(d)
        if not names:
            entries.append(None)
        elif len(names) == 1:
            entries.append(names[0])
        else:
            entries.append(tuple(names))
    return P(*entries)


def _normalize_placements(mesh: ProcessMesh, placements):
    if placements is None:
        return [Replicate() for _ in range(mesh.ndim)]
    placements = list(placements)
    while len(placements) < mesh.ndim:
        placements.append(Replicate())
    return placements


def shard_tensor(data, mesh: ProcessMesh, placements,
                 dtype=None, place=None, stop_gradient=None) -> Tensor:
    """Reference ``auto_parallel/api.py:126``: global tensor -> DistTensor.

    Lays the value out as a ``NamedSharding`` over the mesh; the array stays
    a single global-view ``jax.Array`` whose shards live on the right chips.
    """
    if isinstance(data, Tensor):
        if stop_gradient is None:
            stop_gradient = data.stop_gradient
        val = data._read()
        is_param = isinstance(data, Parameter)
    else:
        val = jnp.asarray(data, dtype=dtype)
        is_param = False
        if stop_gradient is None:
            stop_gradient = True
    placements = _normalize_placements(mesh, placements)
    spec = _to_partition_spec(mesh, placements)
    if not isinstance(val, jax.core.Tracer):
        val = jax.device_put(val, NamedSharding(mesh.jmesh, spec))
    if is_param:
        out = Parameter(val, trainable=not stop_gradient)
    else:
        out = Tensor(val, stop_gradient=stop_gradient)
    out._dist = (mesh, placements)
    if isinstance(data, Tensor) and data.name:
        out.name = data.name
    return out


def dtensor_from_fn(fn: Callable, mesh: ProcessMesh, placements,
                    *args, **kwargs) -> Tensor:
    """Reference ``auto_parallel/api.py`` dtensor_from_fn."""
    return shard_tensor(fn(*args, **kwargs), mesh, placements)


def reshard(x: Tensor, mesh: ProcessMesh, placements) -> Tensor:
    """Reference ``auto_parallel/api.py:304`` + the C++ reshard engine
    (``{r,s,p}_to_{r,s,p}_reshard_function.cc``): here a single
    ``device_put`` — XLA plans the all-gather/slice/all-to-all movement.
    Differentiable: the cotangent reshards back through the same machinery.
    """
    placements = _normalize_placements(mesh, placements)
    spec = _to_partition_spec(mesh, placements)
    sharding = NamedSharding(mesh.jmesh, spec)

    def _reshard_impl(v):
        return jax.device_put(v, sharding)

    out = apply("reshard", _reshard_impl, x)
    out._dist = (mesh, placements)
    return out


def shard_layer(layer: Layer, process_mesh: ProcessMesh,
                shard_fn: Optional[Callable] = None,
                input_fn: Optional[Callable] = None,
                output_fn: Optional[Callable] = None) -> Layer:
    """Reference ``auto_parallel/api.py:403``: convert a Layer's parameters
    to dist tensors in place. ``shard_fn(name, sublayer, mesh)`` mutates
    sublayer params via ``shard_tensor``; default replicates everything."""

    def _default_shard(name, sub, mesh):
        for pname, p in list(sub._parameters.items()):
            if p is not None and not p.is_dist():
                sub._parameters[pname] = _as_dist_param(p, mesh,
                                                       [Replicate()])

    fn = shard_fn or _default_shard
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, process_mesh)
    # shard_fn implementations may have replaced parameter objects wholesale;
    # normalize: any plain Tensor left in _parameters becomes dist-replicated
    for name, sub in layer.named_sublayers(include_self=True):
        for pname, p in list(sub._parameters.items()):
            if p is not None and not p.is_dist():
                sub._parameters[pname] = _as_dist_param(
                    p, process_mesh, [Replicate()])
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda lyr, inputs: input_fn(inputs, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: output_fn(outputs, process_mesh))
    return layer


def _as_dist_param(p: Tensor, mesh, placements) -> Parameter:
    """In-place sharding. ``mesh`` may be a ProcessMesh or a raw jax Mesh
    (fleet layers store the latter); ``placements`` a placement list or a
    ready PartitionSpec."""
    jmesh = mesh.jmesh if isinstance(mesh, ProcessMesh) else mesh
    if isinstance(placements, P):
        spec = placements
    else:
        if isinstance(mesh, ProcessMesh):
            placements = _normalize_placements(mesh, placements)
        spec = _to_partition_spec(mesh, placements)
    v = p._read()
    if isinstance(v, jax.ShapeDtypeStruct):
        # lazy (LazyGuard) parameter: annotate the abstract value
        v = jax.ShapeDtypeStruct(v.shape, v.dtype,
                                 sharding=NamedSharding(jmesh, spec))
    elif not isinstance(v, jax.core.Tracer):
        v = jax.device_put(v, NamedSharding(jmesh, spec))
    # mutate in place so optimizer param identity is preserved
    p._write(v)
    p._dist = (mesh, placements)
    return p


def shard_parameter(p: Tensor, mesh: ProcessMesh, placements) -> Tensor:
    """Convenience used by shard_fn implementations: shard an existing
    Parameter in place (identity-preserving, unlike shard_tensor)."""
    return _as_dist_param(p, mesh, placements)


class _ShardOptimizer:
    """Reference ``auto_parallel/api.py:960`` shard_optimizer: makes the
    optimizer state distributed. Accumulators created by ``zeros_like``
    inherit the parameter's sharding automatically (XLA); ``shard_fn(name,
    param, accumulator) -> placements`` overrides — e.g. ZeRO-style sharding
    of moments along dp."""

    def __init__(self, optimizer, shard_fn=None):
        self._inner = optimizer
        self._shard_fn = shard_fn

    def step(self):
        self._inner.step()
        if self._shard_fn is not None:
            self._apply_shard_fn()

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        out = self._inner.minimize(loss, startup_program, parameters,
                                   no_grad_set)
        if self._shard_fn is not None:
            self._apply_shard_fn()
        return out

    def _apply_shard_fn(self):
        opt = self._inner
        params = {id(p): p for p in getattr(opt, "_parameters", [])}
        for acc_name, store in opt._accumulators.items():
            for pid, acc in store.items():
                p = params.get(pid)
                # a 0-d accumulator (a bias-correction power) has nothing
                # to place: it stays replicated and shard_fn is not asked
                if p is None or acc.is_dist() or not acc.ndim:
                    continue
                mesh = p.process_mesh or _global_mesh
                if mesh is None:
                    continue
                placements = self._shard_fn(acc_name, p, acc)
                if placements is not None:
                    _as_dist_param(acc, mesh, placements)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def shard_optimizer(optimizer, shard_fn=None):
    return _ShardOptimizer(optimizer, shard_fn)


# --- strategy + dist to_static --------------------------------------------

class Strategy:
    """Reference ``auto_parallel/strategy.py``: config container. Most knobs
    (fusion, reshard planning) are XLA's; kept for API parity."""

    class _Flags:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def __init__(self, config=None):
        self.sharding = Strategy._Flags(enable=False, stage=1, degree=8)
        self.fused_passes = Strategy._Flags(enable=False, fused_passes_list=[])
        self.gradient_merge = Strategy._Flags(enable=False, k_steps=1)
        self.pipeline = Strategy._Flags(enable=False, schedule_mode="1F1B",
                                        micro_batch_size=1,
                                        accumulate_steps=1)
        self.amp = Strategy._Flags(enable=False, dtype="bfloat16", level="O2")
        if config:
            for k, v in config.items():
                cur = getattr(self, k, None)
                if isinstance(v, dict) and isinstance(cur, Strategy._Flags):
                    cur.__dict__.update(v)
                else:
                    setattr(self, k, v)


def to_static(layer_or_fn, loader=None, loss=None, optimizer=None,
              strategy=None):
    """Reference ``auto_parallel/api.py`` dist-aware to_static: the regular
    jit capture already compiles sharded steps into one SPMD program, so
    this simply defers to ``paddle_tpu.jit.to_static``."""
    from ... import jit as _jit
    return _jit.to_static(layer_or_fn)
