"""Whole-program jaxpr analyzer: dataflow framework + pass families.

This module turns the PR 1 jaxpr lint into a real program analyzer. It
provides a small dataflow framework over ClosedJaxprs — a labeled
sub-jaxpr walk (pjit / cond / while / scan / custom_vjp / pallas_call),
def-use chains, and per-eqn live ranges — and registers three pass
families alongside the shallow PDT20x checks:

- **PDT22x — collective consistency.** :func:`collective_schedule`
  extracts the ordered collective schedule (psum / ppermute /
  all_gather / ... with axes, shape, dtype) from a program. PDT221
  ERRORs on collectives under divergent ``cond`` branches whose
  schedules differ (an SPMD deadlock: ranks taking different branches
  issue different collective sequences). PDT222 WARNs when an
  axis-size-dependent shape (an ``all_gather`` result) feeds another
  collective — the program silently re-specializes per world size.
  PDT223 is the *runtime* side: :func:`verify_schedule` hashes each
  rank's schedule and cross-checks via the TCP store at group setup,
  catching divergence before the PDT-E021 collective timeout.
- **PDT23x — donation & HBM.** PDT231 ERRORs on read-after-donation
  (a donated input with no shape/dtype-compatible output: its buffer
  is re-used by XLA while the caller may still hold the old handle —
  the orphaned-flat-bucket restore bug class). PDT232 WARNs on
  double-donation (more donated inputs than matching outputs). PDT233
  WARNs on missed donation of *large* (>= 1 MiB) step-carry buffers —
  fused-optimizer flat buckets and engine KV pools are the canonical
  wins. :func:`static_peak_bytes` runs a live-range interval sweep to
  estimate peak HBM per program; the jit layer exposes it as the
  ``hbm.static_peak_bytes{fn}`` gauge next to the measured gauges.
- **PDT24x — recompile risk.** PDT241 WARNs on weak-type promotion
  forks (a weak-typed input hitting a ``convert_element_type`` — the
  same call with a committed array traces differently and forks the
  compile cache). PDT242 is runtime-reported by the jit capture cache
  when one function accumulates >= 3 shape-only signature variants
  (shape-as-data: a traced length/table baked as a static dim — the
  engine's no-recompile contract), and feeds the same
  ``compile.retrace`` event vocabulary as the runtime classifier.

Entry points: :func:`audit_jaxpr` (one ClosedJaxpr),
:func:`audit_executable` (a built ``jit._Executable``; also computes
the static peak estimate), :func:`audit_jitted` (trace a callable with
example args and audit — for raw ``jax.jit`` sites), and
:func:`audit_counts` (process-level per-code tally).
All are mode-gated by ``PDTPU_ANALYSIS`` and never raise except through
the standard ``report`` gate in error mode.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Iterable, Iterator, Optional

from .registry import Severity, register, register_runtime
from . import engine as _engine

# --------------------------------------------------------------------------
# sub-jaxpr walk
# --------------------------------------------------------------------------

# params holding a single sub-jaxpr (ClosedJaxpr or bare Jaxpr)
_SINGLE_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
                        "fun_jaxpr", "fwd_jaxpr_thunk")


def _as_jaxpr(obj):
    """Unwrap to a bare Jaxpr (obj may be a ClosedJaxpr); None if not a
    jaxpr-like object."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner
    if hasattr(obj, "eqns") and hasattr(obj, "invars"):
        return obj
    return None


def subjaxprs(eqn) -> Iterator[tuple[str, object]]:
    """Yield ``(label, jaxpr)`` for every sub-jaxpr of ``eqn``.

    Covers the higher-order primitives the stack actually emits — pjit,
    cond (``branches`` tuple), while (``cond_jaxpr``/``body_jaxpr``),
    scan, custom_vjp/custom_jvp (``call_jaxpr``/``fun_jaxpr``),
    pallas_call — plus a duck-typed fallback so new primitives still get
    walked. Labels are ``"<param>"`` or ``"<param>[i]"`` for tuples
    (e.g. ``"branches[1]"`` = the cond true-branch)."""
    seen: set[int] = set()
    for name, val in eqn.params.items():
        if callable(val) and not hasattr(val, "eqns") \
                and not hasattr(val, "jaxpr"):
            continue  # thunks (fwd_jaxpr_thunk) — don't force them
        j = _as_jaxpr(val)
        if j is not None and id(j) not in seen:
            seen.add(id(j))
            yield name, j
            continue
        if isinstance(val, (list, tuple)):
            for i, item in enumerate(val):
                j = _as_jaxpr(item)
                if j is not None and id(j) not in seen:
                    seen.add(id(j))
                    yield f"{name}[{i}]", j


def all_eqns(jaxpr) -> Iterator[tuple[object, str]]:
    """Every eqn of ``jaxpr`` and its sub-jaxprs with a ``/``-joined
    path label (e.g. ``"body_jaxpr/branches[0]"``)."""
    def walk(j, path):
        for eqn in j.eqns:
            yield eqn, path
            for label, sub in subjaxprs(eqn):
                yield from walk(sub, f"{path}/{label}" if path else label)
    yield from walk(_as_jaxpr(jaxpr) or jaxpr, "")


# --------------------------------------------------------------------------
# def-use chains and live ranges
# --------------------------------------------------------------------------

def _aval_bytes(aval) -> int:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * aval.dtype.itemsize
    except Exception:
        return 0


def _aval_str(aval) -> str:
    try:
        return f"{aval.dtype}[{','.join(str(d) for d in aval.shape)}]"
    except Exception:
        return str(aval)


def def_use(jaxpr) -> dict:
    """Def-use chains for the *top level* of ``jaxpr``: maps each var to
    the list of eqn indices that consume it (outvar uses get index
    ``len(eqns)``). Literals are skipped."""
    j = _as_jaxpr(jaxpr) or jaxpr
    uses: dict = {}
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if hasattr(v, "count"):
                uses.setdefault(v, []).append(i)
    n = len(j.eqns)
    for v in j.outvars:
        if hasattr(v, "count"):
            uses.setdefault(v, []).append(n)
    return uses


def live_ranges(jaxpr) -> dict:
    """Live interval ``var -> (birth, death)`` over top-level eqn
    indices. Inputs are born at -1; values used by an outvar die at
    ``len(eqns)`` (they survive the whole program)."""
    j = _as_jaxpr(jaxpr) or jaxpr
    uses = def_use(j)
    birth: dict = {}
    for v in j.invars + getattr(j, "constvars", []):
        birth[v] = -1
    for i, eqn in enumerate(j.eqns):
        for v in eqn.outvars:
            if hasattr(v, "count"):
                birth.setdefault(v, i)
    out: dict = {}
    for v, b in birth.items():
        us = uses.get(v)
        out[v] = (b, max(us) if us else b)
    return out


def static_peak_bytes(closed, *, donated: Iterable[int] = ()) -> int:
    """Static peak-HBM estimate from a live-range interval sweep.

    Sweeps the top-level eqns accumulating live-set bytes; a sub-jaxpr
    (scan body, cond branch, ...) contributes its own inner peak *minus*
    the operand/result bytes already counted live at the call site.
    Donated inputs whose shape/dtype matches an output are assumed
    aliased by XLA (counted once, not twice). This is an estimate — XLA
    fuses, rematerializes, and pads — but tracks ``program_state +
    transient`` well enough for a 25%-band regression gate."""
    j = _as_jaxpr(closed) or closed
    donated = frozenset(donated)
    ranges = live_ranges(j)
    n = len(j.eqns)

    # bytes XLA saves by aliasing donated inputs onto matching outputs
    out_keys: dict[tuple, int] = {}
    for v in j.outvars:
        aval = getattr(v, "aval", None)
        if aval is None:
            continue
        key = (tuple(getattr(aval, "shape", ())),
               str(getattr(aval, "dtype", "")))
        out_keys[key] = out_keys.get(key, 0) + 1
    aliased = 0
    for i in sorted(donated):
        if i >= len(j.invars):
            continue
        aval = j.invars[i].aval
        key = (tuple(getattr(aval, "shape", ())),
               str(getattr(aval, "dtype", "")))
        if out_keys.get(key, 0) > 0:
            out_keys[key] -= 1
            aliased += _aval_bytes(aval)

    # delta sweep: +bytes at birth, -bytes after death
    deltas = [0] * (n + 2)
    for v, (b, d) in ranges.items():
        size = _aval_bytes(getattr(v, "aval", None))
        if not size:
            continue
        deltas[b + 1] += size
        deltas[d + 2 if d + 2 <= n + 1 else n + 1] -= size

    # inner peaks of sub-jaxprs, attributed at their call eqn
    inner_extra = [0] * (n + 1)
    for i, eqn in enumerate(j.eqns):
        for _, sub in subjaxprs(eqn):
            inner = static_peak_bytes(sub)
            boundary = sum(_aval_bytes(getattr(v, "aval", None))
                           for v in list(eqn.invars) + list(eqn.outvars)
                           if hasattr(v, "aval"))
            extra = inner - boundary
            if extra > 0:
                inner_extra[i + 1] = max(inner_extra[i + 1], extra)

    peak = live = 0
    for i in range(n + 1):
        live += deltas[i]
        peak = max(peak, live + inner_extra[i])
    return max(0, peak - aliased)


# --------------------------------------------------------------------------
# collective schedule
# --------------------------------------------------------------------------

COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
    "axis_index",  # not a transfer, but schedule-ordering relevant: no
})
# axis_index carries no payload; exclude it from the schedule proper
_SCHEDULE_PRIMS = COLLECTIVE_PRIMS - {"axis_index"}


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in a program's ordered schedule."""

    prim: str                 # e.g. "psum"
    axes: tuple               # axis names, e.g. ("pg",)
    shape: tuple              # operand shape
    dtype: str
    path: str = ""            # sub-jaxpr path ("" = top level)

    def key(self) -> tuple:
        return (self.prim, self.axes, self.shape, self.dtype)


def _axes_of(eqn) -> tuple:
    for k in ("axes", "axis_name", "axis"):
        a = eqn.params.get(k)
        if a is not None:
            if isinstance(a, (list, tuple)):
                return tuple(str(x) for x in a)
            return (str(a),)
    return ()


def collective_schedule(closed, *, path: str = "") -> list[CollectiveOp]:
    """Ordered collective schedule of ``closed`` (sub-jaxprs included,
    in program order). Each entry records primitive, axes, operand
    shape/dtype and the sub-jaxpr path for provenance."""
    out: list[CollectiveOp] = []
    for eqn, p in all_eqns(closed):
        if str(eqn.primitive) not in _SCHEDULE_PRIMS:
            continue
        v = eqn.invars[0] if eqn.invars else None
        aval = getattr(v, "aval", None)
        out.append(CollectiveOp(
            prim=str(eqn.primitive), axes=_axes_of(eqn),
            shape=tuple(getattr(aval, "shape", ())),
            dtype=str(getattr(aval, "dtype", "")),
            path=f"{path}/{p}" if path and p else (p or path)))
    return out


def schedule_hash(schedule: list[CollectiveOp]) -> str:
    """Stable hash of a collective schedule (order + op keys; sub-jaxpr
    paths excluded so structurally identical programs agree)."""
    canon = ";".join(
        f"{op.prim}@{','.join(op.axes)}:{op.dtype}"
        f"[{','.join(str(d) for d in op.shape)}]" for op in schedule)
    return hashlib.sha1(canon.encode()).hexdigest()[:16]


# --------------------------------------------------------------------------
# PDT22x — collective consistency
# --------------------------------------------------------------------------

@register(
    "PDT221", "divergent-collective-cond", Severity.ERROR, "ir",
    example="""
import jax
import jax.numpy as jnp
from jax import lax

JAXPR = jax.make_jaxpr(
    lambda p, x: lax.cond(p, lambda v: lax.psum(v, 'i'),
                          lambda v: v * 2.0, x),
    axis_env=[('i', 2)])(True, jnp.ones((4,), jnp.float32))
""",
    near_miss="""
import jax
import jax.numpy as jnp
from jax import lax

JAXPR = jax.make_jaxpr(
    lambda p, x: lax.cond(p, lambda v: lax.psum(v, 'i') * 2.0,
                          lambda v: lax.psum(v, 'i') + 1.0, x),
    axis_env=[('i', 2)])(True, jnp.ones((4,), jnp.float32))
""")
def check_divergent_collective_cond(closed, ctx):
    """``cond`` branches with different collective schedules are an SPMD
    deadlock: when the predicate diverges across ranks (data-dependent
    predicates usually do), one rank enters a psum the other never
    issues, and the program hangs until the collective watchdog's
    PDT-E021 timeout. Either hoist the collective out of the cond or
    make every branch issue the identical schedule."""
    for eqn, path in all_eqns(closed):
        if str(eqn.primitive) != "cond":
            continue
        branches = eqn.params.get("branches") or ()
        scheds = [[op.key() for op in collective_schedule(b)]
                  for b in branches]
        if len(scheds) < 2 or all(s == scheds[0] for s in scheds[1:]):
            continue
        desc = []
        for i, s in enumerate(scheds):
            ops = ", ".join(f"{p}@{','.join(a)}" for p, a, _, _ in s) \
                or "(none)"
            desc.append(f"branch[{i}]: {ops}")
        where = f" (at {path})" if path else ""
        yield (f"cond branches issue divergent collective schedules"
               f"{where} — ranks whose predicate differs will deadlock "
               f"(SPMD): " + "; ".join(desc), eqn)


@register(
    "PDT222", "axis-dependent-shape-collective", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp
from jax import lax

JAXPR = jax.make_jaxpr(
    lambda x: lax.psum(lax.all_gather(x, 'i'), 'i'),
    axis_env=[('i', 2)])(jnp.ones((4,), jnp.float32))
""",
    near_miss="""
import jax
import jax.numpy as jnp
from jax import lax

JAXPR = jax.make_jaxpr(
    lambda x: lax.psum(x, 'i') + lax.all_gather(x, 'i').sum(),
    axis_env=[('i', 2)])(jnp.ones((4,), jnp.float32))
""")
def check_axis_dependent_shape(closed, ctx):
    """A value whose shape depends on the axis size (an ``all_gather``
    result: one dim is ``axis_size * n``) feeding another collective
    means the program's collective payloads silently re-specialize per
    world size — an elastic resize recompiles *and* reshapes every
    rank's schedule. Reduce before gathering, or keep gathered values
    out of later collectives."""
    j = _as_jaxpr(closed) or closed
    axis_dep: set = set()
    for eqn in j.eqns:
        prim = str(eqn.primitive)
        if prim == "all_gather":
            for v in eqn.outvars:
                if hasattr(v, "count"):
                    axis_dep.add(v)
            continue
        if prim in _SCHEDULE_PRIMS:
            for v in eqn.invars:
                if hasattr(v, "count") and v in axis_dep:
                    yield (f"{prim} consumes an axis-size-dependent "
                           f"shape ({_aval_str(v.aval)} from all_gather)"
                           f": collective payloads re-specialize per "
                           f"world size; reduce before gathering", eqn)
                    break
        # propagate the taint through elementwise/reshape-ish ops
        if any(hasattr(v, "count") and v in axis_dep for v in eqn.invars):
            for v in eqn.outvars:
                if hasattr(v, "count"):
                    axis_dep.add(v)


register_runtime(
    "PDT223", "collective-schedule-divergence", Severity.ERROR,
    """Ranks disagree on the collective schedule for the upcoming
    training session: each rank hashed its program's ordered collective
    schedule at group setup and the store cross-check found a mismatch.
    Without this check the divergence surfaces only as a PDT-E021
    collective timeout mid-step. Usually a rank-dependent branch or a
    config skew (different bucket sizes / sync settings per node).""",
    example="""
from paddle_tpu import analysis
from paddle_tpu.analysis import program as prog


class _Store:
    def __init__(self, kv):
        self.kv = kv

    def set(self, k, v):
        self.kv[k] = v

    def get(self, k, timeout=None):
        from paddle_tpu.core.errors import StoreTimeoutError
        if k not in self.kv:
            raise StoreTimeoutError(f"no key {k}")
        return self.kv[k]


kv = {}
s0, s1 = _Store(kv), _Store(kv)
with analysis.collect() as DIAGS:
    prog.verify_schedule(s0, "setup", "node-0", ["node-0", "node-1"],
                         "aaaa", timeout=0.1, raise_on_divergence=False)
    prog.verify_schedule(s1, "setup", "node-1", ["node-0", "node-1"],
                         "bbbb", timeout=0.1, raise_on_divergence=False)
""",
    near_miss="""
from paddle_tpu import analysis
from paddle_tpu.analysis import program as prog


class _Store:
    def __init__(self, kv):
        self.kv = kv

    def set(self, k, v):
        self.kv[k] = v

    def get(self, k, timeout=None):
        from paddle_tpu.core.errors import StoreTimeoutError
        if k not in self.kv:
            raise StoreTimeoutError(f"no key {k}")
        return self.kv[k]


kv = {}
s0, s1 = _Store(kv), _Store(kv)
with analysis.collect() as DIAGS:
    prog.verify_schedule(s0, "setup", "node-0", ["node-0", "node-1"],
                         "aaaa", timeout=0.1, raise_on_divergence=False)
    prog.verify_schedule(s1, "setup", "node-1", ["node-0", "node-1"],
                         "aaaa", timeout=0.1, raise_on_divergence=False)
""")


def verify_schedule(store, tag: str, node_id: str, members: list,
                    sched_hash: str, *, timeout: float = 5.0,
                    raise_on_divergence: bool = True) -> bool:
    """Cross-check ``sched_hash`` against every peer via the store.

    Each rank publishes its hash under ``sched/{tag}/{node}`` and polls
    the peers'. A missing peer (store timeout) is skipped — membership
    churn is the elastic manager's problem, not ours. On mismatch the
    divergence is reported as PDT223 and, with ``raise_on_divergence``,
    a :class:`~paddle_tpu.core.errors.CollectiveScheduleError`
    (PDT-E023) is raised — failing fast at group setup instead of
    hanging until the PDT-E021 watchdog fires mid-step. Returns True
    when every reachable peer agrees."""
    from ..core.errors import CollectiveScheduleError, StoreTimeoutError

    store.set(f"sched/{tag}/{node_id}", str(sched_hash))
    mismatches: list[str] = []
    for peer in members:
        if str(peer) == str(node_id):
            continue
        try:
            theirs = store.get(f"sched/{tag}/{peer}", timeout=timeout)
        except StoreTimeoutError:
            continue  # peer not up yet; elastic membership handles it
        except Exception:
            continue
        if isinstance(theirs, bytes):
            theirs = theirs.decode("utf-8", "replace")
        if str(theirs) != str(sched_hash):
            mismatches.append(f"{peer}={theirs}")
    if not mismatches:
        return True
    msg = (f"collective schedule divergence at group setup "
           f"[{tag}]: this rank ({node_id}) hashed {sched_hash}, "
           f"peers disagree: {', '.join(mismatches)} — ranks would "
           f"deadlock at the first mismatched collective")
    _engine.report_runtime("PDT223", msg, file=f"<store:{tag}>")
    if raise_on_divergence:
        raise CollectiveScheduleError(msg)
    return False


# --------------------------------------------------------------------------
# PDT23x — donation & HBM
# --------------------------------------------------------------------------

def _shape_key(v) -> tuple:
    aval = getattr(v, "aval", None)
    return (tuple(getattr(aval, "shape", ())),
            str(getattr(aval, "dtype", "")))


@register(
    "PDT231", "read-after-donation", Severity.ERROR, "ir",
    example="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda w: w.sum())(jnp.ones((8,), jnp.float32))
DONATED = frozenset({0})
N_ARGS = 0
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(lambda w: w + 1.0)(jnp.ones((8,), jnp.float32))
DONATED = frozenset({0})
N_ARGS = 0
""")
def check_read_after_donation(closed, ctx):
    """A donated input with NO shape/dtype-compatible output: XLA frees
    or reuses its buffer during the step, but nothing replaces it — any
    caller still holding the handle (a state dict, a flat bucket, a KV
    pool) reads garbage on the next step. This is the orphaned-buffer
    restore bug class; donation must pair each donated input with the
    output that supersedes it."""
    j = _as_jaxpr(closed) or closed
    out_count: dict[tuple, int] = {}
    for v in j.outvars:
        key = _shape_key(v)
        out_count[key] = out_count.get(key, 0) + 1
    uses = def_use(j)
    for i in sorted(ctx.donated):
        if i >= len(j.invars):
            continue
        v = j.invars[i]
        if out_count.get(_shape_key(v), 0) == 0:
            # provenance: anchor to the last eqn consuming the donated
            # buffer — the site whose result outlives the freed input
            sites = [k for k in uses.get(v, ()) if k < len(j.eqns)]
            eqn = j.eqns[sites[-1]] if sites else None
            yield (f"input #{i} ({_aval_str(v.aval)}) is donated but no "
                   f"output matches its shape/dtype: its buffer is "
                   f"consumed with nothing superseding it — a caller "
                   f"re-reading the old handle gets garbage "
                   f"(read-after-donation)", eqn)


@register(
    "PDT232", "double-donation", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda a, b: (a + b,))(jnp.ones((8,), jnp.float32),
                           jnp.ones((8,), jnp.float32))
DONATED = frozenset({0, 1})
N_ARGS = 0
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda a, b: (a + b, a - b))(jnp.ones((8,), jnp.float32),
                                 jnp.ones((8,), jnp.float32))
DONATED = frozenset({0, 1})
N_ARGS = 0
""")
def check_double_donation(closed, ctx):
    """More inputs donated for one shape/dtype class than there are
    outputs to alias onto: the surplus donations buy nothing (XLA can
    only alias one input per output buffer) while still invalidating the
    callers' handles. Donate exactly the inputs the outputs supersede."""
    j = _as_jaxpr(closed) or closed
    out_count: dict[tuple, int] = {}
    for v in j.outvars:
        key = _shape_key(v)
        out_count[key] = out_count.get(key, 0) + 1
    don_count: dict[tuple, list] = {}
    for i in sorted(ctx.donated):
        if i >= len(j.invars):
            continue
        don_count.setdefault(_shape_key(j.invars[i]), []).append(i)
    for key, idxs in don_count.items():
        outs = out_count.get(key, 0)
        if outs and len(idxs) > outs:
            v = j.invars[idxs[0]]
            yield (f"{len(idxs)} inputs {idxs} donated for "
                   f"{_aval_str(v.aval)} but only {outs} matching "
                   f"output(s): the surplus donation invalidates a live "
                   f"handle without saving HBM (double-donation)", None)


_BIG = 1 << 20  # 1 MiB — PDT233 only fires on buffers worth donating


@register(
    "PDT233", "missed-donation-step-carry", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda w: w + 1.0)(jnp.ones((1024, 1024), jnp.float32))
DONATED = frozenset()
N_ARGS = 0
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda w: w + 1.0)(jnp.ones((1024, 1024), jnp.float32))
DONATED = frozenset({0})
N_ARGS = 0
""")
def check_missed_donation(closed, ctx):
    """A large (>= 1 MiB) step-carry buffer — a state input whose
    shape/dtype matches an output — not donated doubles its HBM
    footprint: XLA must materialize the new value alongside the old.
    Fused-optimizer flat buckets and engine KV pools are the canonical
    wins (a flat bucket is the model size; a KV pool is the HBM
    budget). PDT203 notes the general case; this WARNs when the wasted
    buffer is big enough to matter."""
    j = _as_jaxpr(closed) or closed
    out_count: dict[tuple, int] = {}
    for v in j.outvars:
        key = _shape_key(v)
        out_count[key] = out_count.get(key, 0) + 1
    for i in sorted(ctx.donated):
        if i < len(j.invars):
            key = _shape_key(j.invars[i])
            if out_count.get(key, 0) > 0:
                out_count[key] -= 1
    for i, v in enumerate(j.invars):
        if i < ctx.n_explicit_args or i in ctx.donated:
            continue
        size = _aval_bytes(getattr(v, "aval", None))
        if size < _BIG:
            continue
        key = _shape_key(v)
        if out_count.get(key, 0) > 0:
            out_count[key] -= 1
            yield (f"state input #{i} ({_aval_str(v.aval)}, "
                   f"{size / (1 << 20):.1f} MiB) matches an output but "
                   f"is not donated: a full extra copy of a step-carry "
                   f"buffer held in HBM across the step", None)


# --------------------------------------------------------------------------
# PDT24x — recompile risk
# --------------------------------------------------------------------------

@register(
    "PDT241", "weak-type-promotion-fork", Severity.WARN, "ir",
    example="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda x, s: x * s)(jnp.ones((4,), jnp.bfloat16), 3.0)
""",
    near_miss="""
import jax
import jax.numpy as jnp

JAXPR = jax.make_jaxpr(
    lambda x, s: x * s)(jnp.ones((4,), jnp.bfloat16),
                        jnp.float32(3.0))
""")
def check_weak_type_promotion_fork(closed, ctx):
    """A weak-typed program input flowing into a dtype conversion: the
    promotion the compiler picked depends on the input being weak, so
    the same call with a committed array traces to a DIFFERENT program
    — a signature fork that doubles the compile cache and can flip
    numerics (bf16 vs f32 accumulation). PDT205 notes weak inputs
    exist; this flags the fork actually happening (eqn-level site).
    Commit the scalar's dtype at the boundary."""
    j = _as_jaxpr(closed) or closed
    weak_invars = {v for v in j.invars
                   if getattr(getattr(v, "aval", None), "weak_type", False)}
    if not weak_invars:
        return
    flagged = 0
    for eqn in j.eqns:
        if str(eqn.primitive) != "convert_element_type":
            continue
        for v in eqn.invars:
            if hasattr(v, "count") and v in weak_invars:
                new = eqn.params.get("new_dtype")
                yield (f"weak-typed input ({_aval_str(v.aval)}) is "
                       f"promoted to {new} inside the program: the same "
                       f"call with a committed array traces differently "
                       f"and forks the compile cache; commit the dtype "
                       f"at the boundary", eqn)
                flagged += 1
                if flagged >= 5:
                    return


register_runtime(
    "PDT242", "shape-as-data-recompile", Severity.WARN,
    """One function accumulated >= 3 compiled variants that differ ONLY
    in input shapes: a traced length/batch/table is being baked into the
    program as a static dim, so every new size recompiles (the engine's
    no-recompile contract is void). Pad to a bucketed shape or pass the
    length as data. Cross-referenced with the runtime
    ``compile.retrace`` cause classifier — both report the same
    vocabulary.""",
    example="""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import analysis


@paddle.jit.to_static
def fn(x):
    return x * 2.0


with analysis.collect() as DIAGS:
    for n in (4, 5, 6):
        fn(paddle.to_tensor(np.ones((n,), np.float32)))
""",
    near_miss="""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import analysis


@paddle.jit.to_static
def fn(x):
    return x * 2.0


with analysis.collect() as DIAGS:
    for n in (4, 5):
        fn(paddle.to_tensor(np.ones((n,), np.float32)))
""")


SHAPE_FORK_LIMIT = 3  # distinct shape-only variants before PDT242 fires


def strip_shapes(sig):
    """Recursively erase shape tuples from a jit cache signature, so
    signatures differing only in shapes collapse to one class. Tensor
    leaves are ``("T", shape, dtype)`` / ``("A", shape, dtype)`` tuples
    (see ``jit._tree_signature``)."""
    if isinstance(sig, tuple):
        if len(sig) == 3 and sig[0] in ("T", "A"):
            return (sig[0], None) + tuple(
                strip_shapes(s) for s in sig[2:])
        return tuple(strip_shapes(s) for s in sig)
    if isinstance(sig, (list, frozenset)):
        return type(sig)(strip_shapes(s) for s in sig)
    return sig


# --------------------------------------------------------------------------
# audit entry points
# --------------------------------------------------------------------------

@dataclasses.dataclass
class AuditResult:
    """What one whole-program audit produced."""

    diags: list
    peak_bytes: int
    schedule: list
    schedule_hash: str
    where: str = "<jaxpr>"


def flat_eqn_count(jaxpr) -> int:
    """Total equation count of a jaxpr INCLUDING every call-like
    sub-jaxpr (pjit, remat/checkpoint, scan, custom_vjp, ...) — the
    denominator-independent size measure a remat A/B needs: a remat
    region's recomputed forward lives in a ``remat``-primitive
    sub-jaxpr, invisible to a top-level count."""
    from jax import core as _jcore  # noqa: F401  (import parity)
    total = 0
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
    for eqn in jaxpr.eqns:
        total += 1
        for v in eqn.params.values():
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                total += flat_eqn_count(v)
            elif isinstance(v, (tuple, list)):
                for item in v:
                    if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                        total += flat_eqn_count(item)
    return total


# process-level per-code tally
_audit_counts: dict[str, int] = {}


def audit_counts(reset: bool = False) -> dict[str, int]:
    """Per-code finding counts accumulated by every audit since the last
    reset: a caller that compiles a set of programs reads here which
    PDT codes their audits raised, and how often."""
    out = dict(sorted(_audit_counts.items()))
    if reset:
        _audit_counts.clear()
    return out


def _tally(diags) -> None:
    for d in diags:
        _audit_counts[d.code] = _audit_counts.get(d.code, 0) + 1


def audit_jaxpr(closed, *, donated: Iterable[int] = (),
                n_explicit_args: int = 0, where: str = "<jaxpr>",
                extra_suppress: frozenset = frozenset(),
                do_report: bool = True) -> AuditResult:
    """Run the full IR pass suite over one ClosedJaxpr and compute the
    program's static peak-HBM estimate and collective schedule.

    The diagnostics go through the standard ``report`` gate (mode flag,
    suppression, session dedup) unless ``do_report=False`` (the CLI
    collects its own)."""
    diags = _engine.check_jaxpr(
        closed, donated=donated, n_explicit_args=n_explicit_args,
        where=where, extra_suppress=extra_suppress)
    try:
        peak = static_peak_bytes(closed, donated=donated)
    except Exception:
        peak = 0
    try:
        sched = collective_schedule(closed)
        shash = schedule_hash(sched)
    except Exception:
        sched, shash = [], ""
    _tally(diags)
    if do_report:
        _engine.report(diags, where=where)
    return AuditResult(diags=diags, peak_bytes=peak, schedule=sched,
                       schedule_hash=shash, where=where)


def audit_executable(exe, *, where: str = "", fn=None
                     ) -> Optional[AuditResult]:
    """Whole-program audit of a built ``jit._Executable`` — the
    post-capture hook ``StaticFunction._capture`` calls once per trace.

    Stashes ``static_peak_bytes`` and ``schedule_hash`` on the
    executable (the jit layer's ``hbm.static_peak_bytes{fn}`` gauge and
    the elastic schedule verifier read them) *before* the capture
    releases the jaxpr. Mode-gated; returns None when the lint is off
    or the jaxpr is already released."""
    if _engine.mode() == "off":
        return None
    closed = getattr(exe, "jaxpr", None)
    if closed is None:
        return None
    extra = frozenset()
    if fn is not None:
        extra = frozenset(getattr(_engine._unwrap_callable(fn),
                                  "__pdtpu_suppress__", frozenset()))
    try:
        res = audit_jaxpr(
            closed, donated=getattr(exe, "donate_idx", ()),
            n_explicit_args=getattr(exe, "n_explicit_args", 0),
            where=where or "<to_static>", extra_suppress=extra,
            do_report=False)
    except Exception:
        _engine.logger.debug("audit_executable failed", exc_info=True)
        return None
    exe.static_peak_bytes = res.peak_bytes
    exe.schedule_hash = res.schedule_hash
    # flattened program size, stashed before the jaxpr is released:
    # remat A/Bs read it off cached executables (the recompute fraction
    # is extra eqns / baseline eqns)
    try:
        exe.jaxpr_eqn_count = flat_eqn_count(closed)
    except Exception:
        exe.jaxpr_eqn_count = 0
    _engine.report(res.diags, where=where)
    return res


def audit_jitted(fn, args=(), kwargs=None, *, where: str = "",
                 donated: Iterable[int] = ()) -> Optional[AuditResult]:
    """Trace ``fn`` with example args and audit the jaxpr — the hook for
    raw ``jax.jit`` sites (engine COW/window programs, pipeline bodies,
    psum_mean) that never pass through ``to_static`` capture.

    Mode-gated and best-effort: tracing failures are swallowed (a
    broken audit must never break a build). When ``donated`` is empty
    the donation passes are disabled by marking every input explicit."""
    if _engine.mode() == "off":
        return None
    try:
        import jax
        closed = jax.make_jaxpr(fn)(*args, **(kwargs or {}))
    except Exception:
        _engine.logger.debug("audit_jitted trace failed (%s)", where,
                             exc_info=True)
        return None
    donated = tuple(donated)
    n_explicit = 0 if donated else len(closed.jaxpr.invars)
    try:
        return audit_jaxpr(closed, donated=donated,
                           n_explicit_args=n_explicit,
                           where=where or getattr(fn, "__name__", "<fn>"))
    except Exception:
        _engine.logger.debug("audit_jitted failed (%s)", where,
                             exc_info=True)
        return None
