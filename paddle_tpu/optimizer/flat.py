"""Flat dtype-bucketed optimizer state — the multi-tensor fused path.

Capability analog of the reference's ``multi_tensor_apply`` family
(``paddle/phi/kernels/fused_adam_kernel.cu``, ``multi_tensor_momentum``):
instead of updating O(num_params) small tensors one at a time, parameters
of one dtype are laid out in a single padded 1-D *flat buffer* per state
class (params, master weights, grads, per-moment accumulators) and the
whole update runs as a handful of fused kernels
(``ops/pallas/fused_optimizer.py``).

Aliasing story (jax.Arrays are immutable, so "views" are logical):

- A :class:`FlatStore` owns one flat storage ``Tensor`` plus per-member
  *view* tensors. A view keeps its public identity (``p``, ``p.grad``,
  ``opt._accumulators[...][pid]``) but its ``_read``/``_write`` funnel
  (``core/tensor.py``) routes here: reads materialize ``flat[off:off+n]``
  lazily (cached against the flat array's identity — jax arrays are
  immutable, so an identity match proves freshness), writes store a
  *local override* that the next ``sync()`` folds back with ONE concat.
- Under jit capture the storage tensor is the program input/output; the
  member views are invisible to the capture (``jit/__init__.py`` filters
  them), so a compiled train step threads a few flat arrays through its
  carry instead of hundreds of per-param arrays.
- GRAD stores are the exception: under a tracker their views read/write
  as plain tensors (the member's own funnel value). Gradients are
  produced per-param by autograd and may legitimately thread per-param
  through captured programs (gradient accumulation); baking a
  storage-slice read into the trace would go stale the moment another
  compiled program accumulates into the per-param value. Eagerly they
  still read through the flat buffer, which is what makes
  ``clear_grad(set_to_zero=True)`` a single ``zeros_like`` on the
  bucket with every view observing the zeros lazily.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import tensor as _tm
from ..core.tensor import Tensor

# flat buffers are padded to a multiple of this many elements so the
# Pallas kernel's (8, 128)-tiled 2-D view needs no per-step padding
ALIGN = 1024


class FlatMismatch(RuntimeError):
    """A member no longer matches its bucket slot (dtype/shape drift,
    e.g. ``amp.decorate`` re-casting after the bucket was built). The
    optimizer responds by defusing back to the per-param path."""


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def _replaying():
    """True under a NON-discovery tracker (the jit replay/trace pass).
    Replay re-executes the step with temporary tracer-backed tensors:
    the store's host-side state (member bindings, local flags, dirty
    bit) must NOT mutate there — only value flow through the tracker's
    env is real. Discovery (step 0, concrete) and eager mutate."""
    tr = _tm._tracker
    return tr is not None and not getattr(tr, "is_discovery", False)


def _concrete(x):
    return isinstance(x, jax.Array) and not _is_tracer(x)


class FlatGroup:
    """One dtype bucket: shared geometry + the per-state-class stores."""

    def __init__(self, params, values, use_master):
        self.params = list(params)
        self.shapes = [tuple(v.shape) for v in values]
        self.sizes = [int(np.prod(s)) if s else 1 for s in self.shapes]
        self.offsets = []
        off = 0
        for n in self.sizes:
            self.offsets.append(off)
            off += n
        self.total = off
        self.padded = -(-off // ALIGN) * ALIGN
        self.dtype = values[0].dtype
        self.use_master = use_master
        self.pids = {id(p): i for i, p in enumerate(self.params)}
        # stores (filled by the optimizer's bucket build)
        self.param_store: Optional[FlatStore] = None
        self.master_store: Optional[FlatStore] = None
        self.moment_stores: dict[str, FlatStore] = {}
        # ONE 0-d beta-pow pair for the bucket, where the per-param path
        # keeps one 0-d pair a parameter; a defuse hands this pair to
        # every member as it is
        self.b1p: Optional[Tensor] = None
        self.b2p: Optional[Tensor] = None
        self.grad_store: Optional[FlatStore] = None

    def flatten(self, values, dtype=None):
        """values (member order) -> one padded flat array (ONE concat)."""
        dt = dtype or values[0].dtype
        pieces = []
        for i, v in enumerate(values):
            if tuple(v.shape) != self.shapes[i]:
                raise FlatMismatch(
                    f"member {i} shape {tuple(v.shape)} != bucket slot "
                    f"{self.shapes[i]}")
            if v.dtype != dt:
                raise FlatMismatch(
                    f"member {i} dtype {v.dtype} != bucket dtype {dt}")
            pieces.append(jnp.ravel(v))
        pad = self.padded - self.total
        if pad:
            pieces.append(jnp.zeros((pad,), dt))
        return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]

    def stores(self):
        out = []
        if self.param_store is not None:
            out.append(self.param_store)
        if self.master_store is not None:
            out.append(self.master_store)
        out.extend(self.moment_stores.values())
        return out


class FlatStore:
    """One flat buffer + its member views (see module docstring)."""

    def __init__(self, group: FlatGroup, kind: str, flat_value):
        self.group = group
        self.kind = kind  # "param" | "master" | "moment" | "grad"
        self.storage = Tensor(flat_value)
        self.storage._flat_view = (self, -1)
        n = len(group.params)
        self.members: list[Optional[Tensor]] = [None] * n
        self.local = [False] * n
        self._dirty = False

    # ---- binding ---------------------------------------------------------
    def bind(self, i: int, t: Tensor):
        """Adopt ``t`` as the view of slot ``i``. The caller guarantees
        ``t``'s current logical value equals the slot's flat slice."""
        t._flat_view = (self, i)
        st = self.storage._data
        t._flat_src = st if _concrete(st) else None
        self.members[i] = t
        self.local[i] = False

    def owns(self, t: Tensor, i: int) -> bool:
        fv = t._flat_view
        return fv is not None and fv[0] is self and fv[1] == i

    def unbind_all(self):
        """Materialize every member into a plain tensor (defuse). Eager
        only — under capture the optimizer raises instead."""
        if _tm._tracker is not None:
            raise FlatMismatch("cannot defuse flat buckets under capture")
        for i, t in enumerate(self.members):
            if t is None or not self.owns(t, i):
                continue
            val = self.member_read(t, i)
            t._flat_view = None
            t._flat_src = None
            t._data = val
            self.members[i] = None
        self.storage._flat_view = None

    # ---- the view funnel (called from Tensor._read/_write) ---------------
    def member_read(self, t: Tensor, i: int):
        tr = _tm._tracker
        if i < 0:  # the storage tensor itself
            if tr is None and self._dirty:
                self.sync()
            return tr.on_read(t) if tr is not None else t._data
        if tr is not None:
            # a tracker that maps THIS member to a value of its own
            # (recompute threads a block's params through
            # jax.checkpoint as explicit inputs; a cond branch hoists
            # its operands) must see the read: a slice of the storage
            # would cut the member out of that trace, and its grad
            # would be zero
            substituted = getattr(tr, "substituted", None)
            if substituted is not None and substituted(t):
                return tr.on_read(t)
            if self.kind == "grad":
                # under capture a grad view is a plain tensor: the trace
                # must consume the member's own (possibly accumulated)
                # value, never a baked storage slice (see module doc).
                # Refresh only under DISCOVERY (concrete): inside a jax
                # trace even a slice of a concrete array is a tracer,
                # and caching one would leak it past the trace.
                if not self.local[i] and not _replaying():
                    self._refresh(t, i)
                return tr.on_read(t)
            if self.local[i]:
                return tr.on_read(t)
            return self._slice(self.storage._read(), i)
        if self.local[i]:
            return t._data
        flat = self.storage._data
        if t._flat_src is flat:
            return t._data
        val = self._slice(flat, i)
        t._data = val
        t._flat_src = flat
        return val

    def member_write(self, t: Tensor, i: int, val):
        tr = _tm._tracker
        if i >= 0 and _replaying() and self.kind != "grad":
            # a local view override cannot compile: discovery's sync()
            # folds it into the storage and resets the host _dirty
            # flag, so the replayed trace would skip the fold and the
            # compiled program silently drops the write. Raising HERE
            # (replay runs inside exe.build's trace-failure net) turns
            # that into the standard decline -> eager fallback, whose
            # concrete discovery output is correct; replay also catches
            # views first bound DURING discovery, where the write
            # preceded binding. Grad views are exempt: backward writes
            # them and the gather always re-reads members under capture.
            from ..jit import GraphBreak
            raise GraphBreak(
                f"write to a fused-bucket {self.kind} view under jit "
                "capture cannot compile — mutate the tensor outside "
                "the captured step, or disable the fused optimizer "
                "path (PDTPU_FUSED_OPT=off)")
        if i >= 0 and not _replaying():
            self.local[i] = True
            self._dirty = True
            t._flat_src = None
        if tr is not None:
            tr.on_write(t, val)
        else:
            t._data = val

    def _refresh(self, t: Tensor, i: int):
        """Bring a stale eager cache up to date from the concrete flat
        (discovery passes read ``t._data`` raw through the tracker)."""
        flat = self.storage._data
        if _concrete(flat) and not _is_tracer(t._data) \
                and t._flat_src is not flat:
            t._data = self._slice(flat, i)
            t._flat_src = flat

    def _slice(self, flat, i):
        g = self.group
        o, n = g.offsets[i], g.sizes[i]
        return flat[o:o + n].reshape(g.shapes[i])

    # ---- flat-level operations ------------------------------------------
    def set_flat(self, val):
        """Replace the whole flat buffer; views re-materialize lazily."""
        self.storage._write(val)
        if not _replaying():
            self.local = [False] * len(self.local)
            self._dirty = False

    def flat_value(self):
        """Current flat value with local member overrides folded in."""
        if self._dirty:
            self.sync()
        return self.storage._read()

    def sync(self):
        """Fold local member overrides back into the flat storage with
        ONE concat (raises FlatMismatch on dtype/shape drift)."""
        if not self._dirty:
            return
        tr = _tm._tracker
        # raw storage read (not through member_read: the storage's own
        # funnel would re-enter this sync on the dirty flag)
        flat = tr.on_read(self.storage) if tr is not None \
            else self.storage._data
        dt = flat.dtype
        vals = []
        for i, t in enumerate(self.members):
            if self.local[i] and t is not None:
                vals.append(tr.on_read(t) if tr is not None else t._data)
            else:
                vals.append(self._slice(flat, i))
        self.set_flat(self.group.flatten(vals, dtype=dt))

    def fill_zeros(self):
        """Zero the flat buffer in ONE op; views observe lazily."""
        self.set_flat(jnp.zeros_like(self.storage._read()))
        tr = _tm._tracker
        if tr is not None:
            # under capture, per-member zero slices (constant-folded by
            # XLA) keep the traced per-param grad values in sync with
            # the zeroed bucket — grad views read as plain tensors there
            zf = self.storage._read()
            for i, t in enumerate(self.members):
                if t is not None and self.owns(t, i):
                    t._write(self._slice(zf, i))
            if not _replaying():
                self.local = [False] * len(self.local)
                self._dirty = False
