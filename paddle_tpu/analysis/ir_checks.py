"""IR front-end: jaxpr/lowered-program checks (``PDT2xx``).

These run over the *traced* program — the ClosedJaxpr a ``to_static``
capture produced (or any jaxpr handed to ``analysis.check_jaxpr``) —
and flag hazards only visible after tracing: dtype promotion the source
never spelled out, blocking host callbacks, buffers that could be
donated but are not, computation that is traced but never used, and
weak-typed inputs that fork the compile cache.

A check is a generator ``check(closed_jaxpr, ctx) -> (message, eqn)``
(``eqn`` may be ``None`` when the finding is program-level); ``ctx``
carries ``donated`` (invar indices), ``n_explicit_args`` and ``where``.
"""
from __future__ import annotations

from .registry import Severity, register, register_runtime

_WIDE_DTYPES = ("float64", "complex128")
_BLOCKING_CALLBACKS = {"pure_callback", "io_callback"}


def _all_eqns(jaxpr):
    """Eqns of ``jaxpr`` and every sub-jaxpr (cond/while/scan bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)  # ClosedJaxpr
            if sub is not None and hasattr(sub, "eqns"):
                yield from _all_eqns(sub)
            elif hasattr(v, "eqns"):         # bare Jaxpr
                yield from _all_eqns(v)
            elif isinstance(v, (list, tuple)):
                for b in v:
                    sub = getattr(b, "jaxpr", None)
                    if sub is not None and hasattr(sub, "eqns"):
                        yield from _all_eqns(sub)


def _aval_str(aval) -> str:
    try:
        return (f"{aval.dtype}[{','.join(str(d) for d in aval.shape)}]")
    except Exception:
        return str(aval)


@register(
    "PDT201", "f64-promotion", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp

with jax.enable_x64(True):
    JAXPR = jax.make_jaxpr(
        lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones((4,), jnp.float32))
""",
    near_miss="""
import jax
import jax.numpy as jnp

with jax.enable_x64(True):
    JAXPR = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((4,), jnp.float32))
""")
def check_f64_promotion(closed, ctx):
    """A float64/complex128 value appearing in a program whose inputs
    are all narrower is an unintended promotion: on TPU f64 is emulated
    (~10x slower) and doubles HBM traffic. Usually a stray Python float
    interacting with x64 mode or an explicit astype."""
    jaxpr = closed.jaxpr
    if any(str(getattr(v.aval, "dtype", "")) in _WIDE_DTYPES
           for v in jaxpr.invars):
        return  # caller fed f64 in on purpose
    for eqn in _all_eqns(jaxpr):
        for v in eqn.outvars:
            if str(getattr(v.aval, "dtype", "")) in _WIDE_DTYPES:
                yield (f"{eqn.primitive} produces {_aval_str(v.aval)} "
                       f"from narrower inputs (f64 is emulated on TPU); "
                       f"check for stray Python floats or astype",
                       eqn)
                return  # promotion cascades; first site is the root


@register(
    "PDT202", "host-callback-in-program", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp
import numpy as np


def f(x):
    return jax.pure_callback(
        lambda v: np.asarray(v) * 2,
        jax.ShapeDtypeStruct((4,), jnp.float32), x)


JAXPR = jax.make_jaxpr(f)(jnp.ones((4,), jnp.float32))
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,), jnp.float32))
""")
def check_host_callback(closed, ctx):
    """A blocking host callback (``pure_callback``/``io_callback``)
    inside a compiled program forces a device->host->device round trip
    every step — on a network-attached TPU that is milliseconds per
    call. Async ``debug_callback`` is exempt."""
    for eqn in _all_eqns(closed.jaxpr):
        if str(eqn.primitive) in _BLOCKING_CALLBACKS:
            yield (f"{eqn.primitive} embeds a blocking host round trip "
                   f"in the compiled program (per-step device->host "
                   f"transfer); keep the computation on device or hoist "
                   f"the callback out of the step", eqn)


@register(
    "PDT203", "undonated-state-buffer", Severity.NOTE, "ir",
    example="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda w: w + 1.0)(jnp.ones((8,), jnp.float32))
DONATED = frozenset()
N_ARGS = 0
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda w: w + 1.0)(jnp.ones((8,), jnp.float32))
DONATED = frozenset({0})
N_ARGS = 0
""")
def check_undonated_state(closed, ctx):
    """A captured state input whose shape/dtype matches an output and is
    not donated costs a full extra buffer of HBM: XLA cannot reuse the
    input allocation for the result. The jit capture donates written
    state automatically — this flags programs built outside that path."""
    jaxpr = closed.jaxpr
    out_count: dict[tuple, int] = {}
    for v in jaxpr.outvars:
        key = (tuple(getattr(v.aval, "shape", ())),
               str(getattr(v.aval, "dtype", "")))
        out_count[key] = out_count.get(key, 0) + 1
    for i in sorted(ctx.donated):
        if i < len(jaxpr.invars):
            v = jaxpr.invars[i]
            key = (tuple(getattr(v.aval, "shape", ())),
                   str(getattr(v.aval, "dtype", "")))
            if out_count.get(key, 0) > 0:
                out_count[key] -= 1
    for i, v in enumerate(jaxpr.invars):
        if i < ctx.n_explicit_args or i in ctx.donated:
            continue  # caller-owned args are never donatable
        key = (tuple(getattr(v.aval, "shape", ())),
               str(getattr(v.aval, "dtype", "")))
        if out_count.get(key, 0) > 0:
            out_count[key] -= 1
            yield (f"state input #{i} ({_aval_str(v.aval)}) matches an "
                   f"output but is not donated: one extra buffer of HBM "
                   f"held across the step", None)


@register(
    "PDT204", "dead-computation", Severity.NOTE, "ir",
    example="""
import jax
import jax.numpy as jnp


def f(x):
    unused = jnp.sin(x) @ jnp.cos(x)
    return x * 2


JAXPR = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
""",
    near_miss="""
import jax
import jax.numpy as jnp


def f(x):
    y = jnp.sin(x) @ jnp.cos(x)
    return x * 2 + y


JAXPR = jax.make_jaxpr(f)(jnp.ones((8, 8), jnp.float32))
""")
def check_dead_computation(closed, ctx):
    """Traced computation whose results never reach an output. XLA DCEs
    it before execution, so it costs compile time rather than step time
    — but it almost always marks a bug: a loss term, metric or update
    the author believes is live and is not."""
    jaxpr = closed.jaxpr
    used = set()
    for v in jaxpr.outvars:
        if hasattr(v, "count"):
            used.add(v)
    dead = []
    for eqn in reversed(jaxpr.eqns):
        effects = getattr(eqn, "effects", None)
        live = bool(effects) or any(v in used for v in eqn.outvars)
        if live:
            for v in eqn.invars:
                if hasattr(v, "count"):   # skip Literals
                    used.add(v)
        else:
            dead.append(eqn)
    for eqn in list(reversed(dead))[:5]:
        yield (f"result of {eqn.primitive} is never used (dead "
               f"computation traced into the program); a loss term or "
               f"update may be silently dropped", eqn)


@register(
    "PDT205", "weak-type-input", Severity.NOTE, "ir",
    example="""
import jax

JAXPR = jax.make_jaxpr(lambda x: x * 2.0)(3.0)
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda x: x * 2.0)(jnp.ones((), jnp.float32))
""")
def check_weak_type(closed, ctx):
    """A weak-typed program input (a Python scalar captured as an
    operand) promotes differently from a committed dtype: the same
    function retraces — and recompiles — when the scalar later arrives
    as a real array. Commit the dtype at the boundary."""
    flagged = 0
    for i, v in enumerate(closed.jaxpr.invars):
        if getattr(v.aval, "weak_type", False):
            yield (f"program input #{i} ({_aval_str(v.aval)}) is "
                   f"weak-typed (python scalar); dtype promotion differs "
                   f"from committed arrays and forks the compile cache",
                   None)
            flagged += 1
            if flagged >= 5:
                return


# --------------------------------------------------------------------------
# runtime-reported codes: producers inside compiled programs call
# ``engine.report_runtime(code, ...)``; the registry entry gives them a
# severity, a doc, and golden snippets the self-test executes for real.
# --------------------------------------------------------------------------

register_runtime(
    "PDT206", "while-trip-bound-truncation", Severity.WARN,
    """The differentiable while_loop lowering (bounded masked scan; XLA
    has no reverse-mode while) hit its trip bound with the predicate
    still true: the result is TRUNCATED. Raise ``max_trip_count`` or
    ``FLAGS_while_grad_max_trip_count``.""",
    example="""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.static.nn import while_loop

w = paddle.to_tensor(np.array([1.0], np.float32))
w.stop_gradient = False


@paddle.jit.to_static
def fn(x):
    w.clear_grad()
    i, y = while_loop(lambda i, y: i < 100.0,
                      lambda i, y: (i + 1.0, y * w),
                      [paddle.to_tensor(np.float32(0.0)), x],
                      max_trip_count=4)
    loss = y.sum()
    loss.backward()
    return loss


with analysis.collect() as DIAGS:
    fn(paddle.to_tensor(np.array([2.0], np.float32)))
""",
    near_miss="""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.static.nn import while_loop

w = paddle.to_tensor(np.array([1.0], np.float32))
w.stop_gradient = False


@paddle.jit.to_static
def fn(x):
    w.clear_grad()
    i, y = while_loop(lambda i, y: i < 3.0,
                      lambda i, y: (i + 1.0, y * w),
                      [paddle.to_tensor(np.float32(0.0)), x],
                      max_trip_count=8)
    loss = y.sum()
    loss.backward()
    return loss


with analysis.collect() as DIAGS:
    fn(paddle.to_tensor(np.array([2.0], np.float32)))
""")
