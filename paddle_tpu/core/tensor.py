"""Eager Tensor façade over jax.Array.

Capability analog of ``paddle::Tensor`` + ``phi::DenseTensor`` +
``egr::AutogradMeta`` (SURVEY C8/C16; reference
``paddle/phi/api/include/tensor.h:82``, ``paddle/phi/core/dense_tensor.h:37``,
``paddle/fluid/eager/autograd_meta.h:61``). The device buffer is a jax.Array
(HBM-resident, managed by PJRT — the allocator story of SURVEY C7 is XLA's);
autograd metadata (stop_gradient, grad, producing Node) lives here.

Tensor math methods are installed by ``paddle_tpu.ops`` (the analog of the
generated pybind method table, ``paddle/fluid/pybind/eager_method.cc``).
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import state
from .dtype import Place, convert_dtype
from ..observability import steptimer as _steptimer
from ..observability import tracing as _tracing


# Active capture tracker (set by paddle_tpu.jit); sees every read/write of
# concrete tensors so whole train steps can be lifted into one XLA program.
# THREAD-LOCAL (ISSUE 15): a capture intercepts only the capturing
# thread's tensor traffic.  With a process-global slot, one rank-thread's
# discovery pass recorded another thread's unrelated eager reads (and
# routed those reads through the foreign tracker), so concurrent
# training loops — the elastic supervisor's multi-rank CPU rig, or any
# two fits in threads — failed nondeterministically with "op structure
# is nondeterministic across calls".  Other modules keep reading
# ``tensor_mod._tracker``; the module-level ``__getattr__`` below
# resolves that name per thread.
class _TrackerSlot(threading.local):
    value = None


_tracker_tls = _TrackerSlot()


def set_tracker(tr):
    old = _tracker_tls.value
    _tracker_tls.value = tr
    return old


def __getattr__(name):
    # PEP 562: ``tensor_mod._tracker`` stays the cross-module read API
    if name == "_tracker":
        return _tracker_tls.value
    raise AttributeError(name)


# process-unique tensor ids for the grad tape (autograd keys grad
# buffers by these).  id() is NOT usable there: a discarded op output
# (e.g. the unused half of a (res, normed) pair) is freed at forward
# time and its id() gets reused by a LATER tensor — whose seeded
# cotangent would then alias onto the dead output's tape slot.
_uid_counter = itertools.count(1)


def _item(value):
    return value.item()


class Tensor:
    __slots__ = ("_data", "_stop_gradient", "_grad", "_node", "_hooks",
                 "_retain_grad", "name", "_dist", "_flat_view",
                 "_flat_src", "_uid", "__weakref__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name=None):
        if isinstance(data, Tensor):
            data = data._read()
        dtype = convert_dtype(dtype)
        if isinstance(data, jax.ShapeDtypeStruct):
            # lazy (LazyGuard) tensor: abstract shape/dtype, no storage
            if dtype is not None and data.dtype != jnp.dtype(dtype):
                data = jax.ShapeDtypeStruct(
                    data.shape, jnp.dtype(dtype),
                    sharding=getattr(data, "sharding", None))
            from . import lazy as _lazy
            _lazy.register(self)
        elif not isinstance(data, jax.Array) and not isinstance(
                data, jax.core.Tracer):
            if dtype is None and isinstance(data, (float, list)) :
                arr = np.asarray(data)
                if arr.dtype == np.float64:
                    dtype = state.DEFAULT_DTYPE
            data = jnp.asarray(data, dtype=dtype)
            if place is not None:
                data = jax.device_put(data, Place(place).device)
        elif dtype is not None and data.dtype != dtype:
            data = data.astype(dtype)
        self._data = data
        self._uid = next(_uid_counter)
        self._stop_gradient = bool(stop_gradient)
        self._grad: Optional[Tensor] = None
        self._node = None
        self._hooks: list = []
        self._retain_grad = False
        self.name = name
        self._dist = None  # (ProcessMesh, placements) when distributed
        # (FlatStore, slot) when this tensor is a view into a flat
        # optimizer bucket (optimizer/flat.py); _flat_src anchors the
        # lazily-materialized cache to the flat array it was sliced from
        self._flat_view = None
        self._flat_src = None
        tr = _tracker_tls.value
        if tr is not None:
            tr.on_create(self)

    # --- raw data access (all ops funnel through here; the jit capture
    # tracker hooks these, cf. SOT's eval-frame interception, SURVEY L9) ---
    def _read(self):
        fv = self._flat_view
        if fv is not None:
            return fv[0].member_read(self, fv[1])
        tr = _tracker_tls.value
        if tr is not None:
            return tr.on_read(self)
        return self._data

    def _write(self, val):
        fv = self._flat_view
        if fv is not None:
            fv[0].member_write(self, fv[1], val)
            return
        tr = _tracker_tls.value
        if tr is not None:
            tr.on_write(self, val)
            return
        self._data = val

    def _adopt(self, other: "Tensor"):
        """In-place semantics: this tensor takes over ``other``'s value and
        grad history (used by ``__setitem__`` / ``add_`` style ops).

        If ``other``'s producing node consumed ``self`` (x.add_(y) pattern),
        the pre-mutation identity is moved onto a ghost tensor so the tape
        doesn't see a self-loop (the reference handles this with inplace
        version counters, ``paddle/fluid/eager/utils.h`` CheckInplace)."""
        new_node = other._node
        if new_node is not None and any(t is self for t in new_node.inputs):
            ghost = Tensor.__new__(Tensor)
            ghost._data = self._data
            ghost._uid = next(_uid_counter)
            ghost._stop_gradient = self._stop_gradient
            ghost._grad = None
            ghost._node = self._node
            ghost._hooks = []
            ghost._retain_grad = False
            ghost.name = None
            ghost._dist = None
            ghost._flat_view = None
            ghost._flat_src = None
            if self._node is not None:
                try:
                    i = self._node.out_ids.index(self._uid)
                    self._node.out_ids[i] = ghost._uid
                except ValueError:
                    pass
            new_node.inputs = [ghost if t is self else t
                               for t in new_node.inputs]
        self._write(other._data if _tracker_tls.value is None
                    else other._read())
        self._node = new_node
        if new_node is not None:
            try:
                idx = new_node.out_ids.index(other._uid)
                new_node.out_ids[idx] = self._uid
            except ValueError:
                pass
        self._stop_gradient = other._stop_gradient

    # --- properties -----------------------------------------------------
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def dtype(self):
        return np.dtype(self._data.dtype)

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def place(self):
        try:
            devs = getattr(self._data, "devices", None)
            if devs is not None:
                return Place(next(iter(devs())))
        except Exception:
            pass
        return Place()

    @property
    def stop_gradient(self):
        return self._stop_gradient

    @stop_gradient.setter
    def stop_gradient(self, v):
        self._stop_gradient = bool(v)

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, g):
        if g is not None and not isinstance(g, Tensor):
            g = Tensor(g)
        self._grad = g

    @property
    def is_leaf(self):
        return self._node is None

    # --- distributed metadata (DistTensor analog, SURVEY D6) -----------
    @property
    def process_mesh(self):
        return self._dist[0] if self._dist is not None else None

    @property
    def placements(self):
        return self._dist[1] if self._dist is not None else None

    def is_dist(self):
        return self._dist is not None

    @property
    def T(self):
        from .. import ops
        return ops.transpose_last2(self)

    @property
    def mT(self):
        from .. import ops
        return ops.transpose_last2(self)

    # --- autograd -------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        from .autograd import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self, set_to_zero=False):
        """Drop (default) or zero the gradient. ``set_to_zero=True`` zeroes
        in place, keeping the grad object's identity stable — required for
        jit-captured gradient accumulation, where the compiled program
        threads the grad buffer as donated state across calls."""
        if set_to_zero and self._grad is not None:
            import jax.numpy as jnp
            z = jnp.zeros_like(self._grad._read())
            # through the write funnel: a grad that is a flat-bucket view
            # (fused optimizer) must record the local override
            self._grad._write(z)
            self._grad._node = None
        else:
            self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            self._grad = Tensor(jnp.zeros_like(self._grad._data))
        else:
            self._grad = None

    def _accumulate_grad(self, g):
        if self._grad is None:
            self._grad = Tensor(g, stop_gradient=True)
        else:
            # accumulate IN PLACE (reference semantics: grads accumulate
            # into the same var). Keeping the grad object's identity stable
            # also lets the jit capture thread it as program state.
            try:
                base = self._grad._read()
            except Exception as e:
                if type(e).__name__ == "GraphBreak":
                    raise type(e)(
                        "gradient existed before capture: cross-call grad "
                        "accumulation cannot compile — clear_grad() before "
                        "the captured call, or zero grads inside the "
                        "captured function (clear_grad(set_to_zero=True))"
                    ) from e
                raise
            acc = base + g
            self._grad._write(acc)
            self._grad._node = None
        tr = _tracker_tls.value
        if tr is not None:
            tr.on_grad_write(self)

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Removable:
            def remove(self_inner):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass
        return _Removable()

    def retain_grads(self):
        self._retain_grad = True

    def detach(self) -> "Tensor":
        return Tensor(self._read(), stop_gradient=True)

    def detach_(self) -> "Tensor":
        self._node = None
        self._stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        from .. import ops
        return ops.assign(self)

    # --- host interop ---------------------------------------------------
    def _host(self, convert):
        """``convert`` of this tensor's value, on the host: where the
        eight methods below wait for the device.  Outside a capture the
        wait is the span ``tensor.readback`` and, for a device array, a
        row of the read log (``observability/steptimer.py``); under a
        capture nothing is recorded."""
        if _tracker_tls.value is not None:
            return convert(self._read())
        with _tracing.span("tensor.readback") as wait:
            value = self._read()
            out = convert(value)
            if wait.t0 and isinstance(value, jax.Array):
                _steptimer.note_read(wait.t0, time.perf_counter_ns())
        return out

    def numpy(self) -> np.ndarray:
        return self._host(np.asarray)

    def item(self):
        return self._host(_item)

    def tolist(self):
        return self._host(np.asarray).tolist()

    def __array__(self, dtype=None):
        a = self._host(np.asarray)
        return a.astype(dtype) if dtype is not None else a

    def __dlpack__(self, *a, **k):
        return self._read().__dlpack__(*a, **k)

    # --- python protocol ------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __bool__(self):
        return self._host(bool)

    def __float__(self):
        return self._host(float)

    def __int__(self):
        return self._host(int)

    def __index__(self):
        return self._host(int)

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        sg = self._stop_gradient
        try:
            body = repr(np.asarray(self._data))
            body = body[body.index("(") + 1: body.rindex(")")] if "(" in body else body
        except Exception:
            body = f"<traced {self._data}>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"stop_gradient={sg},\n       {body})")

    # numpy precedence
    __array_priority__ = 100


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """``paddle.to_tensor`` analog (reference
    ``python/paddle/tensor/creation.py``)."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


class Parameter(Tensor):
    """Trainable tensor. Analog of ``paddle.base.framework.Parameter`` /
    ``EagerParamBase`` (reference ``python/paddle/base/framework.py``)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer",
                 "is_distributed", "need_clip", "no_sync")

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.is_distributed = False
        self.need_clip = True
        self.no_sync = False
