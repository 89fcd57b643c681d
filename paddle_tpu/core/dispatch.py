"""Op dispatch: every framework op funnels through `apply`.

Capability analog of the PHI kernel dispatch + eager ad-function codegen
(SURVEY C9/C15/C16; reference ``paddle/phi/core/kernel_factory.h:316``
SelectKernelOrThrowError and the generated ``*_ad_func`` forward functions of
``eager_gen.py``): unwrap tensors, run the XLA-lowered compute, and — when any
differentiable input requires grad — record a jax.vjp node on the tape
(linearised at backward time in eager, as it is recorded under a
``jit.to_static`` capture).

There is no KernelKey{backend,layout,dtype} selection: XLA owns backend and
layout; dtype promotion is jnp's. That whole reference subsystem collapses
into this one file by design.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import scope as _scope
from . import state
from .autograd import Node
from .tensor import Tensor

_TRACER_TYPES = (jax.core.Tracer,)
_amp_mod = None  # lazily bound paddle_tpu.amp (breaks the import cycle)


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.result_type(x), jnp.inexact)


def _flatten(args):
    """Shallow-flatten args: Tensors may appear directly or inside one level
    of list/tuple (concat/stack take tensor lists)."""
    tensors = []
    spec = []
    for a in args:
        if isinstance(a, Tensor):
            spec.append(("t", len(tensors)))
            tensors.append(a)
        elif isinstance(a, (list, tuple)) and any(
                isinstance(x, Tensor) for x in a):
            inner = []
            for x in a:
                if isinstance(x, Tensor):
                    inner.append(("t", len(tensors)))
                    tensors.append(x)
                else:
                    inner.append(("c", x))
            spec.append(("seq", type(a), inner))
        else:
            spec.append(("c", a))
    return tensors, spec


def _rebuild(spec, vals):
    out = []
    for s in spec:
        if s[0] == "t":
            out.append(vals[s[1]])
        elif s[0] == "c":
            out.append(s[1])
        else:
            _, typ, inner = s
            seq = [vals[i[1]] if i[0] == "t" else i[1] for i in inner]
            out.append(list(seq) if typ is list else tuple(seq))
    return out


def _check_nan_inf(name, vals):
    for v in vals:
        if isinstance(v, _TRACER_TYPES):
            return
        if jnp.issubdtype(v.dtype, jnp.inexact):
            if not bool(jnp.all(jnp.isfinite(v))):
                raise FloatingPointError(
                    f"Operator '{name}' output contains NaN/Inf "
                    f"(FLAGS check_nan_inf; reference analog "
                    f"paddle/fluid/eager/nan_inf_utils.h)")


# set by paddle_tpu.profiler while recording: fn(name, t0_ns, t1_ns)
_profile_hook = None


def _reraise_with_op_context(name, vals, e):
    """Attach operator context (SURVEY C2 enforce): which op, what
    operand shapes/dtypes. Framework errors and jit-capture control-flow
    exceptions pass through untouched."""
    from . import errors as _errors
    if isinstance(e, _errors.EnforceNotMet):
        raise
    # GraphBreak etc. steer the jit fallback machinery — never wrap
    if type(e).__name__ == "GraphBreak":
        raise
    wrapped = _errors.InvalidArgumentError(
        _errors.op_error_context(name, vals, e))
    wrapped.op_name = name  # machine-readable op id alongside error_code
    raise wrapped from e


def apply(name: str, fn: Callable, *args, **kwargs):
    """Run op ``fn`` over (unwrapped) args; record grad node if needed.

    Keyword args are static attributes; a Tensor passed as a kwarg is
    unwrapped to its value (read through the jit tracker) but NOT
    differentiated — ops must take differentiable operands positionally.
    """
    hook = _profile_hook   # local: the profiler may clear it mid-op
    if hook is not None:
        import time as _time
        _t0 = _time.perf_counter_ns()
        try:
            return _apply(name, fn, *args, **kwargs)
        finally:
            # an observer must never fail the op it observes: a raising
            # hook would mask the op's own result/exception
            try:
                hook(name, _t0, _time.perf_counter_ns())
            except Exception:
                pass
    return _apply(name, fn, *args, **kwargs)


def _apply(name: str, fn: Callable, *args, **kwargs):
    tensors, spec = _flatten(args)
    vals = [t._read() for t in tensors]
    if kwargs:
        kwargs = {k: (v._read() if isinstance(v, Tensor) else v)
                  for k, v in kwargs.items()}

    # AMP O1/O2 cast (analog of the generated ad_func AMP block, SURVEY C16)
    global _amp_mod
    if _amp_mod is None:
        from .. import amp as _amp_mod_imported
        _amp_mod = _amp_mod_imported
    if _amp_mod.amp_state().enabled:
        vals = _amp_mod.amp_cast_inputs(name, vals)

    grad_on = state.is_grad_enabled()
    diff_idx = [i for i, t in enumerate(tensors)
                if grad_on and not t.stop_gradient and _is_float(vals[i])]

    if not diff_idx:
        try:
            out_vals = fn(*_rebuild(spec, vals), **kwargs)
        except Exception as e:
            _reraise_with_op_context(name, vals, e)
        return _wrap_outputs(name, out_vals, node=None, any_grad=False)

    def pure(*dvals):
        merged = list(vals)
        for i, dv in zip(diff_idx, dvals):
            merged[i] = dv
        return fn(*_rebuild(spec, merged), **kwargs)

    # WHERE the op is linearised follows from whether a program is being
    # captured (core/scope.py: jit's replay of a to_static function).
    #
    # Under capture: jax.vjp here, as the op is recorded.  The forward
    # is traced once and its residuals belong to the forward pass.  The
    # other order (plain forward now, jax.vjp from the saved inputs at
    # backward time) traces every op twice into the one program and
    # leaves XLA to merge the copies.  On the v5e it did not merge the
    # Pallas flash forward, fc1 + GELU or attn/proj: 35.9 ms of a
    # 230.7 ms GPT-2-medium step was forward work run again inside the
    # backward (PERF.md, PR 26/27), and a recompute block's policy saved
    # nothing past its own backward, because the block's residual-saving
    # forward was traced there.
    #
    # In eager: run the plain forward now; jax.vjp happens at backward
    # time from the saved input values (autograd.run_backward).
    # An eager jax.vjp per op costs many times a plain dispatch, so
    # grad-enabled forwards that never reach a backward (eval loops,
    # branch probes) must not pay it. The trade: a backwarded op re-runs
    # its primal inside jax.vjp (fwd executes twice), and eager is
    # dispatch-bound.  A capture that records nodes and never runs a
    # backward traces linearisations XLA deletes.
    capturing = _scope.current() is not None
    diff_vals = [vals[i] for i in diff_idx]
    try:
        if capturing:
            out_vals, vjp_fn = jax.vjp(pure, *diff_vals)
            _scope.tape().record += 1
        else:
            out_vals, vjp_fn = fn(*_rebuild(spec, vals), **kwargs), None
    except Exception as e:
        _reraise_with_op_context(name, vals, e)
    out, node_outs = _wrap_outputs(name, out_vals, node=..., any_grad=True)
    node = Node(
        name, vjp_fn,
        inputs=[tensors[i] for i in diff_idx],
        out_ids=[o._uid for o in node_outs],
        out_avals=[jax.ShapeDtypeStruct(o._data.shape, o._data.dtype)
                   for o in node_outs],
        pure=pure,
        seq_type=(tuple if isinstance(out_vals, tuple)
                  else list if isinstance(out_vals, list) else None),
        diff_vals=None if capturing else diff_vals)
    for o in node_outs:
        o._node = node
    return out


def _wrap_outputs(name, out_vals, node, any_grad):
    if state.get_flag("check_nan_inf"):
        flat = out_vals if isinstance(out_vals, (tuple, list)) else [out_vals]
        _check_nan_inf(name, [v for v in flat if hasattr(v, "dtype")])

    def mk(v):
        t = Tensor(v)
        if any_grad and _is_float(v):
            t._stop_gradient = False
        return t

    if isinstance(out_vals, (tuple, list)):
        outs = [mk(v) for v in out_vals]
        if node is None:
            return (tuple(outs) if isinstance(out_vals, tuple) else outs)
        return (tuple(outs) if isinstance(out_vals, tuple) else outs), outs
    t = mk(out_vals)
    if node is None:
        return t
    return t, [t]


def primitive(name_or_fn=None, name: str | None = None):
    """Decorator turning a pure jnp function into a framework op.

    The decorated function's positional args may be Tensors (or lists of
    Tensors); keyword args are static attributes (analog of op Attrs).
    """
    def deco(fn, opname=None):
        opname = (opname or fn.__name__).lstrip("_")
        for suffix in ("_impl",):
            if opname.endswith(suffix):
                opname = opname[: -len(suffix)]

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            return apply(opname, fn, *args, **kwargs)

        wrapper.raw = fn  # un-wrapped (jax-level) implementation
        return wrapper

    if callable(name_or_fn):
        return deco(name_or_fn)
    return lambda fn: deco(fn, name_or_fn or name)


def unwrap(x):
    """Tensor|array|scalar -> jax value."""
    if isinstance(x, Tensor):
        return x._read()
    return x


def wrap(v, stop_gradient=True) -> Tensor:
    return Tensor(v, stop_gradient=stop_gradient)
