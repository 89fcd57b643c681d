"""Define-by-run autograd engine over jax.vjp.

Capability analog of the reference eager autograd (SURVEY C16:
``paddle/fluid/eager/grad_node_info.h:197`` GradNodeBase/Edge,
``paddle/fluid/eager/backward.cc:105`` RunBackward queue engine,
``tensor_wrapper.h`` forward-tensor saving) — but TPU-native: instead of
hand-written grad kernels, every op records the ``jax.vjp`` linearization of
its XLA computation, and the backward engine is the same reverse topological
queue walk with per-tensor consumer counting.
"""
from __future__ import annotations

import contextlib
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import scope as _scope
from . import state


class Node:
    """One recorded op in the grad graph. Analog of ``egr::GradNodeBase``.

    ``vjp_fn`` is None on a node recorded in eager: the linearization is
    built LAZILY at backward time from ``pure`` + ``diff_vals`` (the
    forward-time input snapshot), so grad-enabled forwards that never
    backward pay no jax.vjp cost.  A node recorded while a program is
    captured carries the ``vjp_fn`` of its one traced forward and no
    ``diff_vals`` (``core/dispatch.py`` says why)."""

    __slots__ = ("name", "vjp_fn", "inputs", "out_ids", "out_avals",
                 "consumed", "pure", "seq_type", "diff_vals", "scope")

    def __init__(self, name, vjp_fn, inputs, out_ids, out_avals, pure=None,
                 seq_type=None, diff_vals=None):
        self.name = name
        self.vjp_fn = vjp_fn
        self.inputs = inputs        # diff-input Tensors (strong refs = TensorWrapper)
        self.out_ids = out_ids      # ._uid of each output tensor (never
                                    # reused, unlike id() of a freed one)
        self.out_avals = out_avals  # ShapeDtypeStruct per output
        self.pure = pure            # primal fn of the diff inputs (for create_graph)
        self.seq_type = seq_type    # None | tuple | list: primal output pytree
        self.diff_vals = diff_vals  # input values for lazy linearization
        self.consumed = False
        # the phase-scope path live when the op was recorded (None
        # outside a capture): backward re-enters it under "backward"
        self.scope = _scope.current()

    def pack_cots(self, cots):
        if self.seq_type is None:
            return cots[0]
        return self.seq_type(cots)

    def __repr__(self):
        return f"<Node {self.name} n_in={len(self.inputs)} n_out={len(self.out_ids)}>"


def _zero_cotangent(aval):
    if jnp.issubdtype(aval.dtype, jnp.floating) or jnp.issubdtype(
        aval.dtype, jnp.complexfloating
    ):
        return jnp.zeros(aval.shape, aval.dtype)
    # Non-differentiable (int/bool) outputs take float0 cotangents under jax.vjp.
    return np.zeros(aval.shape, dtype=jax.dtypes.float0)


def _accum(buf, key, val):
    old = buf.get(key)
    buf[key] = val if old is None else old + val


def _val(g):
    from .tensor import Tensor

    return g._read() if isinstance(g, Tensor) else g


def _cast(g, dtype):
    from .tensor import Tensor

    if isinstance(g, Tensor):
        return Tensor(g._read(), dtype=dtype, stop_gradient=g.stop_gradient)
    return g.astype(dtype)


def _vjp_through_dispatch(n, out_grads):
    """create_graph path: re-linearize the primal so the backward op itself
    is recorded on the tape (double/higher-order grad — the analog of the
    reference's double_grad node generation in eager_gen.py)."""
    from . import dispatch
    from .tensor import Tensor

    float_pos = [i for i, a in enumerate(n.out_avals)
                 if jnp.issubdtype(a.dtype, jnp.inexact)]
    g_args = [out_grads[i] if isinstance(out_grads[i], Tensor)
              else Tensor(out_grads[i]) for i in float_pos]
    n_g = len(g_args)
    avals, pure = n.out_avals, n.pure

    def call(*a):
        gs, xs = a[:n_g], a[n_g:]
        full, gi = [], iter(gs)
        for i, av in enumerate(avals):
            if i in float_pos:
                full.append(next(gi))
            else:
                full.append(np.zeros(av.shape, dtype=jax.dtypes.float0))
        _, vjp = jax.vjp(pure, *xs)
        return tuple(vjp(n.pack_cots(full)))

    outs = dispatch.apply("grad::" + n.name, call, *g_args, *n.inputs)
    if not isinstance(outs, (tuple, list)):
        outs = (outs,)
    return list(outs)


def run_backward(tensors, grad_tensors=None, retain_graph=False, accumulate=True,
                 inputs=None, create_graph=False):
    """Reverse-walk the recorded graph from ``tensors``.

    Mirrors ``egr::RunBackward`` (reference ``paddle/fluid/eager/backward.cc:105``):
    seed output grads, count consumers, queue-process nodes whose outputs are
    final, accumulate leaf grads.

    If ``accumulate`` write ``.grad`` on leaves; always returns a dict
    ``tensor._uid -> grad array`` for tensors in ``inputs`` (paddle.grad
    path).
    """
    from .tensor import Tensor

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    grad_buf: dict[int, Any] = {}
    keepalive: dict[int, Tensor] = {}

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient and t._node is None:
            raise RuntimeError(
                "backward() called on a tensor with stop_gradient=True and no "
                "grad graph")
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g = jnp.ones(t.shape, t.dtype)
        else:
            g = g._read() if isinstance(g, Tensor) else jnp.asarray(g)
        if create_graph:
            g = Tensor(g, stop_gradient=True)
        _accum(grad_buf, t._uid, g)
        keepalive[t._uid] = t

    # --- build reachable node set (walk producers through inputs) ---
    reachable: set[int] = set()
    nodes: dict[int, Node] = {}
    stack = [t._node for t in tensors if t._node is not None]
    while stack:
        n = stack.pop()
        if id(n) in reachable:
            continue
        if n.consumed:
            raise RuntimeError(
                f"grad graph for op '{n.name}' already freed; pass "
                "retain_graph=True to backward through it again")
        reachable.add(id(n))
        nodes[id(n)] = n
        for ti in n.inputs:
            if ti._node is not None:
                stack.append(ti._node)

    # consumer_count[tensor_uid] = reachable nodes consuming that tensor
    consumer_count: dict[int, int] = {}
    for n in nodes.values():
        for ti in n.inputs:
            consumer_count[ti._uid] = consumer_count.get(ti._uid, 0) + 1
            keepalive[ti._uid] = ti

    # node_wait[node] = its outputs that still have pending consumers
    node_wait: dict[int, int] = {}
    producer_of: dict[int, Node] = {}
    for n in nodes.values():
        for oid in n.out_ids:
            producer_of[oid] = n
        node_wait[id(n)] = sum(
            1 for oid in n.out_ids if consumer_count.get(oid, 0) > 0)

    processed: list[Node] = []
    queue = [n for n in nodes.values() if node_wait[id(n)] == 0]

    finalized: set[int] = set()

    def finalize(tid):
        """All consumers of tensor tid processed: its grad is final."""
        if tid in finalized:
            return
        finalized.add(tid)
        t = keepalive.get(tid)
        if t is None:
            return
        g = grad_buf.get(tid)
        if g is not None and t._hooks:
            for h in t._hooks:
                out = h(g if isinstance(g, Tensor) else _wrap_grad(t, g))
                if out is not None:
                    g = out if isinstance(out, Tensor) else jnp.asarray(out)
            grad_buf[tid] = g
        is_leaf = t._node is None
        if accumulate and g is not None and not t.stop_gradient and (
                is_leaf or t._retain_grad):
            t._accumulate_grad(_val(g))
        prod = producer_of.get(tid)
        if prod is not None and id(prod) in node_wait:
            node_wait[id(prod)] -= 1
            if node_wait[id(prod)] == 0:
                queue.append(prod)

    def process(n):
        out_grads = []
        for oid, aval in zip(n.out_ids, n.out_avals):
            g = grad_buf.get(oid)
            if g is None:
                g = _zero_cotangent(aval)
            elif _val(g).dtype != aval.dtype and jnp.issubdtype(
                    aval.dtype, jnp.floating):
                g = _cast(g, aval.dtype)
            out_grads.append(g)
        if create_graph and n.pure is not None:
            # re-linearised here whatever the node holds: the backward
            # op itself goes on the tape
            _scope.tape().backward += 1
            cots = _vjp_through_dispatch(n, out_grads)
        else:
            out_grads = [_val(g) for g in out_grads]
            if n.vjp_fn is None:  # lazy: linearize on first backward
                _scope.tape().backward += 1
                try:
                    _, n.vjp_fn = jax.vjp(n.pure, *n.diff_vals)
                except Exception as e:
                    from . import errors as _errors
                    raise _errors.InvalidArgumentError(
                        _errors.op_error_context(
                            "grad::" + n.name, n.diff_vals, e)) from e
            cots = n.vjp_fn(n.pack_cots(out_grads))
        processed.append(n)
        for ti, cot in zip(n.inputs, cots):
            from .tensor import Tensor as _T
            if cot is not None and not (
                    not isinstance(cot, _T) and hasattr(cot, "dtype")
                    and cot.dtype == jax.dtypes.float0):
                _accum(grad_buf, ti._uid, cot)
            consumer_count[ti._uid] -= 1
            if consumer_count[ti._uid] == 0:
                finalize(ti._uid)

    # a backward op reads backward/<the path its forward was recorded
    # under>, whether its linearisation is built here or was eager
    with _scope.phase("backward"):
        while queue:
            n = queue.pop()
            with _scope.phase(n.scope):
                process(n)
        # Seed tensors with no reachable consumers are final too (leaf
        # seeds).
        for t in tensors:
            if consumer_count.get(t._uid, 0) == 0:
                finalize(t._uid)

    if not retain_graph:
        for n in processed:
            n.vjp_fn = None
            n.inputs = ()
            n.pure = None  # frees the closure pinning forward buffers
            n.diff_vals = None
            n.consumed = True

    if inputs is not None:
        return {t._uid: grad_buf.get(t._uid) for t in inputs}
    return None


def _wrap_grad(t, g):
    from .tensor import Tensor

    return Tensor(g, stop_gradient=True)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, allow_unused=False):
    """``paddle.grad`` analog (reference ``python/paddle/autograd/``):
    grads of outputs w.r.t. inputs without touching ``.grad``."""
    from .tensor import Tensor

    if isinstance(outputs, Tensor):
        outputs = [outputs]
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if retain_graph is None:
        retain_graph = create_graph
    res = run_backward(outputs, grad_outputs, retain_graph=retain_graph,
                       accumulate=False, inputs=inputs,
                       create_graph=create_graph)
    grads = []
    for t in inputs:
        g = res.get(t._uid)
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "one of the input tensors receives no gradient "
                    "(set allow_unused=True to get None)")
            grads.append(None)
        elif isinstance(g, Tensor):
            grads.append(g)
        else:
            grads.append(Tensor(g, stop_gradient=not create_graph))
    return grads


@contextlib.contextmanager
def no_grad():
    old = state.set_grad_enabled(False)
    try:
        yield
    finally:
        state.set_grad_enabled(old)


@contextlib.contextmanager
def enable_grad():
    old = state.set_grad_enabled(True)
    try:
        yield
    finally:
        state.set_grad_enabled(old)


class set_grad_enabled(contextlib.ContextDecorator):
    def __init__(self, mode: bool):
        self._mode = mode
        self._old = None

    def __enter__(self):
        self._old = state.set_grad_enabled(self._mode)
        return self

    def __exit__(self, *exc):
        state.set_grad_enabled(self._old)
        return False
