"""paddle_tpu.jit — whole-step compilation of eager code.

Capability analog of the reference dy2static stack (SURVEY L9:
``paddle.jit.to_static`` ``python/paddle/jit/api.py:135``; the SOT bytecode
tracer ``jit/sot/``; compile cache ``symbolic/compile_cache.py``) — but
TPU-native in mechanism: instead of bytecode simulation producing a
StatementIR that feeds a ProgramDesc executor, we *capture* the eager
tape-level reads/writes of framework state while re-running the function
under ``jax.jit`` tracing, producing one fused XLA program per input
signature. Graph breaks (data-dependent Python control flow) fall back to
eager, mirroring SOT's fallback semantics.

How it works (see also ``core/tensor.py`` ``_tracker``):
1. Discovery pass — the function runs eagerly once (this *is* step 0) while
   a tracker records: which pre-existing Tensors are read (program inputs:
   params, optimizer state, RNG key, batch args), which are written
   (state outputs: updated params/moments/BN stats/RNG), and which tensors
   the function returns.
2. A pure function over (input values) -> (explicit outputs + state outputs)
   is wrapped in ``jax.jit`` with state inputs donated (in-place update on
   TPU HBM, the analog of the reference's inplace address reuse in
   ``inplace_pass.cc``).
3. Cached invocations read the current values of the captured input tensors,
   run the compiled program, and write state outputs back — no Python op
   dispatch at all in steady state.

Every capture is audited ONCE by the whole-program jaxpr analyzer
(``analysis/program.py``: collective-schedule consistency, donation/
live-range HBM with a static peak estimate surfaced as the
``hbm.static_peak_bytes{fn}`` gauge, recompile risk — the cache also
reports PDT242 when >= 3 variants differ only in input shapes) before
the jaxpr is released; gated by ``PDTPU_ANALYSIS``, zero per-dispatch
work.
"""
from __future__ import annotations

import logging
import os
import time
import warnings
import weakref
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core import scope as _scope
from ..core import state
from ..core import tensor as tensor_mod
from ..core.tensor import Tensor
from ..observability import steptimer as _obs_steptimer
from ..observability import tracing as _obs_tracing
from ..observability.metrics import enabled as _obs_enabled

logger = logging.getLogger("paddle_tpu.jit")
# the ring of compiled calls: ``_Executable.__call__`` stores its row
_call_log = _obs_steptimer._calls


# --- compile/HBM observability (ISSUE 12) ----------------------------------
# Every built _Executable registers here (weak: programs die with their
# StaticFunction cache) so lazy gauges can answer "how many bytes of
# captured state do the live compiled programs pin" without any work on
# the hot path — the gauges read at snapshot/render time only.
_live_executables: "weakref.WeakSet" = weakref.WeakSet()


def _program_state_bytes(fn_name=None) -> int:
    """Captured-state bytes (params/opt state/RNG the program holds
    strong refs to) across live executables — per ``fn_name`` when
    given, process-total otherwise."""
    total = 0
    for exe in list(_live_executables):
        if fn_name is not None \
                and getattr(exe, "_fn_name", None) != fn_name:
            continue
        for t in exe.capt_state:
            v = getattr(t, "_data", None)
            nb = getattr(v, "nbytes", None)
            if nb:
                total += int(nb)
    return total


def _jax_live_bytes():
    """Process-total bytes of live jax arrays (HBM residency on a real
    device; host memory on CPU).  Read LAZILY at snapshot time."""
    return int(sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in jax.live_arrays()))


def _static_peak_bytes(fn_name):
    """Largest static peak-HBM estimate among live executables of
    ``fn_name`` (stamped by ``analysis.audit_executable`` at capture;
    max, not sum — each executable is one program's peak, and shape
    variants of one function share buffers across dispatches)."""
    peak = 0
    for exe in list(_live_executables):
        if getattr(exe, "_fn_name", None) != fn_name:
            continue
        peak = max(peak, int(getattr(exe, "static_peak_bytes", 0) or 0))
    return peak


def _register_hbm_gauges(fn_name):
    """Lazy HBM-accounting gauges in the default registry: one
    ``hbm.program_state_bytes`` series per compiled-function name (the
    process total is the sum over the ``fn`` label — a same-name
    unlabeled twin would collide in ``snapshot()``'s nesting) plus the
    ``hbm.live_bytes`` process total (the ISSUE 12 blind spot — pool
    bytes were visible, program residency was not)."""
    from ..observability import metrics as _obs
    reg = _obs.registry()
    reg.gauge("hbm.program_state_bytes", labels={"fn": str(fn_name)},
              help="captured-state bytes pinned by live compiled "
                   "programs (lazy; sum over fn = process total)"
              ).set_function(lambda n=str(fn_name):
                             _program_state_bytes(n))
    reg.gauge("hbm.live_bytes",
              "process-total live jax array bytes (lazy)"
              ).set_function(_jax_live_bytes)
    reg.gauge("hbm.static_peak_bytes", labels={"fn": str(fn_name)},
              help="static peak-HBM estimate from the whole-program "
                   "audit's live-range sweep (analysis/program.py; "
                   "compare against the measured program_state_bytes)"
              ).set_function(lambda n=str(fn_name):
                             _static_peak_bytes(n))


def _note_retrace(exe, sig):
    """Emit a ``compile.retrace`` ring event with a best-effort CAUSE:
    which input positions changed signature since the first trace, or
    — when the signature is identical — the cache-miss/scan-re-trace
    class the jit guards warn about.  A steady-state stream of these
    is the retrace regression ``train.retraces`` counts."""
    from ..observability import events as _events
    from ..observability import metrics as _obs
    if not _obs.enabled():
        return
    base = getattr(exe, "_sig0", None)
    if base is None or len(base) != len(sig):
        cause = "input arity changed"
    else:
        diffs = [i for i, (a, b) in enumerate(zip(base, sig))
                 if a != b]
        if diffs:
            changed = ", ".join(
                f"arg{i}: {base[i][0]}/{base[i][1]} -> "
                f"{sig[i][0]}/{sig[i][1]}" for i in diffs[:3])
            cause = f"input signature changed ({changed})"
        else:
            cause = ("same signature (jit cache miss/eviction or "
                     "scan/window re-trace)")
    _events.emit("compile.retrace",
                 fn=getattr(exe, "_fn_name", "step"),
                 count=int(exe.trace_count), cause=cause)


def _tree_signature(obj):
    """Cache key component for one argument."""
    if isinstance(obj, Tensor):
        d = obj._data
        return ("T", tuple(d.shape), str(d.dtype))
    from ..nn import Layer
    if isinstance(obj, Layer):
        # train/eval flips change the traced program (dropout, BN): guard on
        # the mode vector (the analog of SOT's guard system)
        return ("L", id(obj), obj.training,
                tuple(l.training for l in obj.sublayers()))
    if isinstance(obj, (list, tuple)):
        return (type(obj).__name__,
                tuple(_tree_signature(o) for o in obj))
    if isinstance(obj, dict):
        return ("d", tuple(sorted(
            (k, _tree_signature(v)) for k, v in obj.items())))
    if isinstance(obj, (np.ndarray, jax.Array)):
        return ("A", tuple(obj.shape), str(obj.dtype))
    return ("c", obj if isinstance(obj, (int, float, str, bool,
                                         type(None))) else str(obj))


def _flatten_tensors(obj, out):
    if isinstance(obj, Tensor):
        out.append(obj)
    elif isinstance(obj, (list, tuple)):
        for o in obj:
            _flatten_tensors(o, out)
    elif isinstance(obj, dict):
        for k in sorted(obj):
            _flatten_tensors(obj[k], out)
    return out


class GraphBreak(Exception):
    pass


def _flat_member(t, touched):
    """True for per-param views into a NON-grad flat optimizer bucket
    whose storage participates in this capture — their state lives in
    the bucket storage, so the program must not thread them. A view
    whose bucket the program never touched (e.g. params still bound to
    an old optimizer's bucket while a new one runs per-param) is acting
    as a plain tensor and stays threaded."""
    fv = t._flat_view
    return (fv is not None and fv[1] >= 0 and fv[0].kind != "grad"
            and id(fv[0].storage) in touched)


def _state_write(t, val):
    """Post-execution state write-back: direct for plain tensors, via
    the funnel for flat-bucket views (records the local override so
    later reads see the new value instead of a stale bucket slice)."""
    if t._flat_view is not None:
        t._write(val)
    else:
        t._data = val
    t._node = None


def _scrub_leaked_tracers(discovery):
    """Replay re-executes the function, so the tape may assign tracer-backed
    grad Tensors onto real (pre-existing) tensors. Drop any such leftovers —
    the compiled program returns grads explicitly via grad_out_owners."""
    seen = list(discovery.inputs) + list(discovery.written.values()) + \
        list(discovery.grad_owners.values())
    for t in seen:
        g = t._grad
        if g is not None and isinstance(g._data, jax.core.Tracer):
            t._grad = None
        if t._node is not None:
            t._node = None


class _DiscoveryTracker:
    """Concrete-value pass: classifies tensors into inputs/state/fresh while
    the function executes for real (step 0)."""

    is_discovery = True  # flat-bucket host state may mutate (flat.py)

    def __init__(self):
        self.inputs: list[Tensor] = []      # pre-existing, read
        self.input_ids: set[int] = set()
        self.written: dict[int, Tensor] = {}  # pre-existing, written
        self.fresh: set[int] = set()        # created during capture
        self.grad_owners: dict[int, Tensor] = {}
        self.host_syncs: list[Callable] = []

    def on_create(self, t):
        self.fresh.add(id(t))

    def on_read(self, t):
        tid = id(t)
        if tid not in self.fresh and tid not in self.input_ids:
            self.input_ids.add(tid)
            self.inputs.append(t)
        return t._data

    def on_write(self, t, val):
        tid = id(t)
        if tid in self.fresh:
            # A tensor created during capture but mutated through the state
            # funnel is persistent state born lazily on step 0 (e.g.
            # optimizer accumulators): promote it to a real program
            # input/output so later steps thread it instead of re-creating.
            self.fresh.discard(tid)
            self.input_ids.add(tid)
            self.inputs.append(t)
        self.written[tid] = t
        t._data = val

    def on_grad_write(self, t):
        if id(t) not in self.fresh:
            self.grad_owners[id(t)] = t

    def add_host_sync(self, fn):
        self.host_syncs.append(fn)


class _ReplayTracker:
    """Tracing pass: substitutes jax tracers for the discovered inputs."""

    is_discovery = False  # flat-bucket host state frozen (flat.py)

    def __init__(self, input_ids_to_pos, vals):
        self.pos = input_ids_to_pos
        self.vals = vals
        self.env: dict[int, Any] = {}
        self.fresh: set[int] = set()
        self.grad_owners: dict[int, Tensor] = {}

    def on_create(self, t):
        self.fresh.add(id(t))

    def on_read(self, t):
        tid = id(t)
        if tid in self.env:
            return self.env[tid]
        if tid in self.pos:
            return self.vals[self.pos[tid]]
        if tid in self.fresh:
            return t._data
        # Tensor not seen during discovery (nondeterministic structure)
        raise GraphBreak(
            "tensor read not seen during discovery (op structure is "
            "nondeterministic across calls)")

    def on_write(self, t, val):
        self.env[id(t)] = val

    def on_grad_write(self, t):
        if id(t) not in self.fresh:
            self.grad_owners[id(t)] = t

    def add_host_sync(self, fn):
        pass  # collected once, during discovery


class _Executable:
    """One compiled specialization (per input signature). Holds strong refs
    to the captured state tensors (params/opt state/RNG) — the analog of the
    reference partial program's persistable-var scope."""

    def __init__(self, fn, discovery, ret_rebuild, n_ret):
        self.fn = fn
        self.discovery = discovery
        self.compiled = None
        self.capt_state: list[Tensor] = []
        self.state_out_tensors: list[Tensor] = []
        self.grad_out_owners: list[Tensor] = []
        self.ret_rebuild = ret_rebuild
        self.n_ret = n_ret
        self.arg_out_pos: list[int] = []
        self.trace_count = 0  # XLA (re)traces; guards retrace regressions
        self.jaxpr = None            # ClosedJaxpr, kept for the IR lint
        self.donate_idx: tuple = ()  # donated invar positions
        self.n_explicit_args = 0     # leading caller-owned inputs
        # grad nodes of the newest trace by where the tape linearised
        # them (core/scope.TapeCounts): ``backward`` stays 0 unless the
        # step backwards through a graph recorded outside the capture
        # or asks for create_graph
        self.tape_nodes = None
        self._fn_name = getattr(fn, "__name__", "step")
        self._fn_id = _obs_steptimer.register_fn(self._fn_name)

    def state_split(self):
        """(carry_idx, const_idx) into ``capt_state``: which captured
        tensors the step WRITES (must thread through a scan carry) vs
        reads only (scan constants). Shared by ``jit.multi_step`` and
        the decode-window scan (``models/generation.py``)."""
        pos = {id(t): i for i, t in enumerate(self.capt_state)}
        carry_idx = [pos[id(t)] for t in self.state_out_tensors]
        carry_set = set(carry_idx)
        const_idx = [i for i in range(len(self.capt_state))
                     if i not in carry_set]
        return carry_idx, const_idx

    def build(self, arg_tensors, call_args, call_kwargs):
        d = self.discovery
        arg_pos = {id(t): i for i, t in enumerate(arg_tensors)}
        # tensors that became flat-bucket member views during discovery
        # (the fused optimizer binding params/moments at its first step)
        # are dropped: the flat storage is the program input/output and
        # their traced reads route there. GRAD views stay — under a
        # tracker they read/write as plain tensors (optimizer/flat.py),
        # so gradient accumulation threads per-param exactly as before.
        touched = {id(t) for t in d.inputs}
        touched.update(d.written)
        self.capt_state = [t for t in d.inputs
                           if id(t) not in arg_pos
                           and not _flat_member(t, touched)]
        ordered = list(arg_tensors) + self.capt_state
        pos = {id(t): i for i, t in enumerate(ordered)}

        # mutated explicit-arg tensors are written back BY POSITION to the
        # tensors of the *current* call, not the step-0 objects
        written = [t for t in d.written.values() if id(t) not in arg_pos
                   and not _flat_member(t, touched)]
        self.arg_out_pos = [arg_pos[id(t)] for t in d.written.values()
                            if id(t) in arg_pos]
        written_args = [t for t in d.written.values() if id(t) in arg_pos]
        grad_owners = list(d.grad_owners.values())
        self.state_out_tensors = written
        self.grad_out_owners = grad_owners
        fn = self.fn

        def pure(*vals):
            self.trace_count += 1
            # signature of this trace's inputs: the retrace-cause diff
            # (compile.retrace event) compares against the first one
            sig = tuple((tuple(jnp.shape(v)),
                         str(getattr(v, "dtype", type(v).__name__)))
                        for v in vals)
            if self.trace_count == 1:
                self._sig0 = sig
            else:
                _note_retrace(self, sig)
            tr = _ReplayTracker(pos, vals)
            old = tensor_mod.set_tracker(tr)
            try:
                # phase scopes (core/scope.py) are entered only here,
                # while the program is being captured
                with _scope.capture() as tape:
                    self.tape_nodes = tape
                    out = fn(*call_args, **call_kwargs)
            finally:
                tensor_mod.set_tracker(old)
            ret_vals = []
            for t in _flatten_tensors(out, []):
                ret_vals.append(tr.env.get(id(t), t._data))
            state_vals = [tr.env.get(id(t), t._data) for t in written]
            arg_vals = [tr.env.get(id(t), t._data) for t in written_args]
            grad_vals = []
            for t in grad_owners:
                g = t._grad
                if g is None:
                    grad_vals.append(jnp.zeros_like(t._data))
                else:
                    # in-place accumulated grads live in the replay env
                    # (object identity stable); fresh grads hold tracers
                    grad_vals.append(tr.env.get(id(g), g._data))
            return (tuple(ret_vals) + tuple(state_vals) + tuple(arg_vals) +
                    tuple(grad_vals))

        # donate captured-state inputs that are also outputs (HBM buffer
        # reuse — the analog of the reference inplace_pass). Explicit args
        # are never donated: the caller still owns those buffers.
        written_ids = {id(t) for t in written}
        n_args = len(arg_tensors)
        donate = tuple(i for i, t in enumerate(ordered)
                       if i >= n_args and id(t) in written_ids)
        # the program is named after the user's function
        # (``jit_train_step`` in the trace's "XLA Modules" line and in
        # every op_name), not ``jit_pure``.  The module's name is part
        # of the persistent cache's key where op_name metadata is not,
        # so no run is served an executable compiled before the phase
        # scopes existed.
        pure.__name__ = pure.__qualname__ = self._fn_name
        self._pure = pure  # re-used by jit.multi_step's scanned window
        self.compiled = jax.jit(pure, donate_argnums=donate)
        self.donate_idx = donate
        self.n_explicit_args = n_args
        # force tracing now so failures surface at capture time. The replay
        # re-executes the function body, so host-side grad slots can be
        # clobbered (clear_grad() + backward() replaces a concrete step-0
        # grad with a tracer-backed Tensor): snapshot and restore them.
        # The trace+lower runs under a "compile" tracing span (ISSUE 12)
        # carrying the program geometry, and its wall time backs the
        # train.compile_ms histogram — the single-process blind spot
        # that made recompiles invisible in step timelines.
        from ..observability import metrics as _obs_metrics
        saved_grads = [(t, t._grad) for t in grad_owners]
        t0 = time.perf_counter()
        try:
            with _obs_tracing.span("compile", fn=self._fn_name,
                                   n_inputs=len(ordered),
                                   n_state=len(written),
                                   n_donated=len(donate)) as compile_span:
                traced = self.compiled.trace(*[t._data for t in ordered])
                self.jaxpr = traced.jaxpr
                tape = self.tape_nodes
                compile_span.note(tape_nodes_record=tape.record,
                                  tape_nodes_backward=tape.backward)
                traced.lower()
        finally:
            _scrub_leaked_tracers(d)
            for t, g in saved_grads:
                if t._grad is not g:
                    t._grad = g
        _live_executables.add(self)
        if _obs_metrics.enabled():
            _obs_metrics.registry().histogram(
                "train.compile_ms",
                "trace+lower wall time of captured programs",
                _obs_metrics.LATENCY_BUCKETS_MS).observe(
                    (time.perf_counter() - t0) * 1e3)
            _register_hbm_gauges(self._fn_name)
            for where in ("record", "backward"):
                _obs_metrics.registry().counter(
                    "train.tape_nodes",
                    "grad nodes of captured programs by where the tape "
                    "linearised them (counted as the program is traced)",
                    labels={"linearised": where}).inc(getattr(tape, where))

    def __call__(self, arg_tensors, t_enter=0):
        """Run the compiled program.  Under ``PDTPU_METRICS`` the call
        is row ``n`` of the call log (``observability/steptimer.py``):
        ``t_enter``, when ``StaticFunction.__call__`` was entered, the
        clock the three inner spans took as they opened, and one mark
        more; its ``to_static.call`` span carries the same ``n`` (0 with
        the flag off: no row)."""
        span, log = _obs_tracing.span, _call_log
        on = _obs_enabled()
        n = next(log.numbers) if on else 0
        with span("to_static.call", fn=self._fn_name, n=n):
            with span("to_static.read_state") as read:
                for sync in self.discovery.host_syncs:
                    sync()
                vals = [t._read() for t in arg_tensors] + \
                    [t._read() for t in self.capt_state]
            with span("to_static.launch") as launch:
                outs = self.compiled(*vals)
            with span("to_static.write_state") as write:
                out = self._write_state(arg_tensors, outs)
            if on:
                log.pack(log.buf, ((n - 1) % log.size) * log.stride, n,
                         self._fn_id, t_enter or read.t0, read.t0,
                         launch.t0, write.t0, time.perf_counter_ns())
        return out

    def _write_state(self, arg_tensors, outs):
        n_ret = self.n_ret
        n_state = len(self.state_out_tensors)
        n_arg_out = len(self.arg_out_pos)
        ret_vals = outs[:n_ret]
        state_vals = outs[n_ret:n_ret + n_state]
        arg_vals = outs[n_ret + n_state:n_ret + n_state + n_arg_out]
        grad_vals = outs[n_ret + n_state + n_arg_out:]
        for t, v in zip(self.state_out_tensors, state_vals):
            _state_write(t, v)
        # mutated explicit-arg tensors: write back positionally onto the
        # tensors of THIS call (not the step-0 objects)
        for pos, v in zip(self.arg_out_pos, arg_vals):
            _state_write(arg_tensors[pos], v)
        for t, v in zip(self.grad_out_owners, grad_vals):
            if t._grad is not None:
                # mutate in place so the object identity the trace captured
                # stays valid across XLA retraces (sharding changes);
                # funnel for flat-bucket grad views
                _state_write(t._grad, v)
            else:
                t._grad = Tensor(v, stop_gradient=True)
        if "PADDLE_PROGRESS_FILE" in os.environ:
            # hang-watchdog heartbeat: every completed compiled step
            # (see distributed/elastic.py)
            from ..distributed.elastic import report_progress
            report_progress()
        return self.ret_rebuild([Tensor(v) for v in ret_vals])


def _make_rebuilder(out):
    """fn(list_of_ret_tensors) -> structure shaped like ``out``."""
    if isinstance(out, Tensor):
        return lambda ts: ts[0]
    if isinstance(out, (list, tuple)):
        typ = type(out)

        def rebuild(ts, _out=out, _typ=typ):
            res, i = [], 0
            for o in _out:
                if isinstance(o, Tensor):
                    res.append(ts[i])
                    i += 1
                else:
                    res.append(o)
            return _typ(res)
        return rebuild
    if isinstance(out, dict):
        def rebuild_d(ts, _out=out):
            # sorted: must mirror _flatten_tensors' dict walk order
            res, i = {}, 0
            for k in sorted(_out):
                if isinstance(_out[k], Tensor):
                    res[k] = ts[i]
                    i += 1
                else:
                    res[k] = _out[k]
            return res
        return rebuild_d
    return lambda ts, _out=out: _out


_fallback_retry_limit = 3


def set_fallback_retry_limit(n: int) -> None:
    """How many failed trace attempts before a cache key is pinned to eager
    (the retry policy the reference's SOT gets from guard invalidation;
    a transient failure — OOM, flaky host callback — no longer poisons the
    key forever). Default 3."""
    global _fallback_retry_limit
    _fallback_retry_limit = max(1, int(n))


def get_fallback_retry_limit() -> int:
    return _fallback_retry_limit


class StaticFunction:
    """Analog of ``SymbolicStaticFunction``
    (reference ``jit/dy2static/program_translator.py:708``)."""

    def __init__(self, fn, build_strategy=None, backend=None,
                 full_graph=False, remat=None):
        self.fn = fn
        self._cache: dict[Any, _Executable] = {}
        self._fallback_keys: set = set()
        self._fallback_counts: dict[Any, int] = {}
        self._full_graph = full_graph
        self.__name__ = getattr(fn, "__name__", "static_fn")
        self._conv_fn = None
        self._conv_tried = False
        # resolved 1-tuple (policy,) from to_static(remat=...), or None.
        # Applied AFTER dy2static conversion (see _converted): wrapping
        # before it would hand dy2static a wrapper whose source/closure
        # don't match the user function.
        self._remat = remat
        self._remat_fn = None

    def _converted(self):
        """The dy2static AST-converted function (plain Python if/while/for
        on tensor predicates lowered to cond/while_loop — see
        ``jit/dy2static.py``), or the original when conversion found
        nothing to do or declined. Converted lazily on first call so
        closure cells are populated."""
        if not self._conv_tried:
            # pre-conversion tracer-safety lint (PDT1xx); a no-op when
            # PDTPU_ANALYSIS=off, raises StaticAnalysisError under
            # =error. Runs BEFORE _conv_tried is set: a blocked call
            # must not burn the one conversion attempt, so a later
            # suppressed/fixed call still converts.
            from .. import analysis as _analysis
            _analysis.lint_callable(self.fn, where=self.__name__)
            self._conv_tried = True
            try:
                from .dy2static import convert_function
                self._conv_fn = convert_function(self.fn)
            except Exception as e:
                from ..core.errors import StaticAnalysisError
                if isinstance(e, StaticAnalysisError):
                    # the conversion-decline gate (PDTPU_ANALYSIS=error)
                    # must propagate, and the blocked call must not burn
                    # the one conversion attempt
                    self._conv_tried = False
                    raise
                warnings.warn(
                    f"to_static: dy2static conversion of {self.__name__} "
                    f"failed ({type(e).__name__}: {e}); using the "
                    "original function")
                self._conv_fn = None
        fn = self._conv_fn or self.fn
        if self._remat is None:
            return fn
        if self._remat_fn is None:
            from ..distributed.fleet.recompute import recompute
            pol = self._remat[0]

            def _remat_fn(*args, **kw):
                return recompute(fn, *args, policy=pol, **kw)
            _remat_fn.__name__ = self.__name__
            self._remat_fn = _remat_fn
        return self._remat_fn

    def __get__(self, instance, owner):
        # bound-method support for @to_static on Layer methods
        import functools
        if instance is None:
            return self
        bound = functools.partial(self.__call__, instance)
        bound.__wrapped__ = self
        return bound

    def _cache_key(self, args, kwargs):
        from .. import amp
        a = amp.amp_state()
        return (tuple(_tree_signature(x) for x in args),
                tuple(sorted((k, _tree_signature(v))
                             for k, v in kwargs.items())),
                a.enabled, str(a.dtype), a.level,
                state.is_grad_enabled())

    def __call__(self, *args, **kwargs):
        t_enter = time.perf_counter_ns()    # the call log's first mark
        if tensor_mod._tracker is not None:
            # nested to_static: inline into the outer capture
            return self._converted()(*args, **kwargs)
        try:
            key = self._cache_key(args, kwargs)
        except Exception:
            return self._converted()(*args, **kwargs)
        if key in self._fallback_keys:
            return self._converted()(*args, **kwargs)
        exe = self._cache.get(key)
        arg_tensors = _flatten_tensors((list(args), kwargs), [])
        if exe is not None:
            return exe(arg_tensors, t_enter)
        return self._capture(key, args, kwargs, arg_tensors)

    def _capture(self, key, args, kwargs, arg_tensors):
        fn = self._converted()
        d = _DiscoveryTracker()
        old = tensor_mod.set_tracker(d)
        try:
            out = fn(*args, **kwargs)
        finally:
            tensor_mod.set_tracker(old)
        # a grad owner whose grad is None at function exit was cleared
        # in-function (opt.clear_grad): it is not a program output — and
        # writing a value back would desync eager state from the captured
        # program (stale grads then break later retraces)
        d.grad_owners = {k: t for k, t in d.grad_owners.items()
                         if t._grad is not None}
        ret_tensors = _flatten_tensors(out, [])
        exe = _Executable(fn, d, _make_rebuilder(out),
                          len(ret_tensors))
        try:
            exe.build(arg_tensors, args, kwargs)
        except Exception as e:  # trace failed -> eager, retry next call
            if self._full_graph:
                raise
            n = self._fallback_counts.get(key, 0) + 1
            self._fallback_counts[key] = n
            limit = _fallback_retry_limit
            if n >= limit:
                warnings.warn(
                    f"to_static: pinning {self.__name__} to eager after "
                    f"{n} failed traces ({type(e).__name__}: {e})")
                self._fallback_keys.add(key)
            else:
                warnings.warn(
                    f"to_static: eager fallback for {self.__name__}, "
                    f"trace retry {n}/{limit} on next call "
                    f"({type(e).__name__}: {e})")
            return out
        self._fallback_counts.pop(key, None)
        # post-capture whole-program audit (PDT2xx: collective
        # consistency, donation/HBM with the static peak estimate,
        # recompile risk) over the traced program. Runs BEFORE caching:
        # under PDTPU_ANALYSIS=error a blocking finding leaves the key
        # uncached, so every call re-captures and raises again until the
        # finding is fixed or suppressed. The jaxpr is only needed here
        # — release it so cached executables of large models don't pin
        # the whole trace for the process lifetime (the audit stashes
        # ``static_peak_bytes``/``schedule_hash`` on the exe first).
        from .. import analysis as _analysis
        _analysis.audit_executable(exe, where=self.__name__, fn=self.fn)
        exe.jaxpr = None
        self._cache[key] = exe
        self._check_shape_fork(key)
        return out  # discovery pass already produced step-0 results

    def _check_shape_fork(self, key):
        """PDT242: >= SHAPE_FORK_LIMIT cached variants differing ONLY in
        input shapes means a length/batch/table is baked as a static
        dim — every new size recompiles. Compile-time-only work (runs
        once per new cache entry) sharing the ``compile.retrace`` cause
        vocabulary with the runtime classifier."""
        from ..analysis import program as _program
        try:
            stripped = _program.strip_shapes(key)
            variants = sum(1 for k in self._cache
                           if _program.strip_shapes(k) == stripped)
        except Exception:
            return
        if variants < _program.SHAPE_FORK_LIMIT:
            return
        from .. import analysis as _analysis
        cause = (f"shape-as-data: {variants} compiled variants of "
                 f"{self.__name__} differ only in input shapes")
        _analysis.report_runtime(
            "PDT242",
            f"{cause} — a traced length/table is baked as a static dim "
            f"(every new size recompiles); pad to bucketed shapes or "
            f"pass the length as data", file=f"<jit:{self.__name__}>")
        from ..observability import events as _events
        from ..observability import metrics as _obs
        if _obs.enabled():
            _events.emit("compile.retrace", fn=self.__name__,
                         count=int(variants), cause=cause)

    def concrete_program(self, *args, **kwargs):
        return self._cache.get(self._cache_key(args, kwargs))

    @property
    def code(self):
        import inspect
        try:
            return inspect.getsource(self.fn)
        except OSError:
            return "<source unavailable>"


def aot_lower(fn, *args, donate_state=True, **kwargs):
    """Ahead-of-time lower ``fn``'s captured train-step program WITHOUT
    executing it: the same discovery capture as ``to_static`` runs with
    abstract values, so LazyGuard-built models lower at scales whose
    real parameters exceed host memory (the 13B-on-32-virtual-devices
    proof runs the REAL ``GPTForCausalLM`` + ``shard_gpt`` capture, not
    a hand-written twin). Returns a ``jax.stages.Lowered``;
    ``.compile().memory_analysis()`` gives the per-device picture.

    Inputs = explicit ``args`` tensors + every live lazy tensor
    (shardings from their annotations). With ``donate_state`` the lazy
    state written by the step (parameters under an optimizer update) is
    donated, matching the executable path's buffer reuse. Tensors
    CREATED inside (optimizer moments on their first step) lower as
    outputs — same residency, but not yet aliased inputs as in the
    steady-state program."""
    import jax as _jax

    from ..core import lazy as _lazy

    if isinstance(fn, StaticFunction):
        fn = fn._converted()
    arg_tensors = _flatten_tensors((list(args), kwargs), [])
    arg_ids = {id(t) for t in arg_tensors}
    lazies = [t for t in _lazy.lazy_tensors() if id(t) not in arg_ids]
    tensors = list(arg_tensors) + lazies

    def spec_of(t):
        v = t._data
        if isinstance(v, _jax.ShapeDtypeStruct):
            return v
        sh = getattr(v, "sharding", None)
        from jax.sharding import NamedSharding
        return _jax.ShapeDtypeStruct(
            jnp.shape(v), v.dtype,
            sharding=sh if isinstance(sh, NamedSharding) else None)

    specs = [spec_of(t) for t in tensors]
    holder = {}

    def drive(*vals):
        saved = [(t, t._data, t._grad, t._node) for t in tensors]
        for t, v in zip(tensors, vals):
            t._data = v
        d = _DiscoveryTracker()
        old = tensor_mod.set_tracker(d)
        try:
            # the program to_static would compile: phase scopes, and
            # the tape linearising each op as it is recorded
            with _scope.capture():
                out = fn(*args, **kwargs)
            ret_vals = [t._data for t in _flatten_tensors(out, [])]
            written = [t for t in d.written.values()]
            state_vals = [t._data for t in written]
            holder["written_ids"] = {id(t) for t in written}
        finally:
            tensor_mod.set_tracker(old)
            _scrub_leaked_tracers(d)
            for t, v, g, n in saved:
                t._data = v
                t._grad = g
                t._node = n
        return tuple(ret_vals) + tuple(state_vals)

    if not donate_state:
        return _jax.jit(drive).lower(*specs)
    # trace once to learn which state the step writes, then lower with
    # those inputs donated (the _Executable donates the same way)
    _jax.eval_shape(drive, *specs)
    donate = tuple(i for i, t in enumerate(tensors)
                   if i >= len(arg_tensors)
                   and id(t) in holder["written_ids"])
    return _jax.jit(drive, donate_argnums=donate).lower(*specs)


def _resolve_remat(policy):
    """Validate ``to_static(remat=...)`` and return the
    ``fleet.recompute`` policy object (``None`` spells 'full': save
    nothing, recompute everything). The wrap itself happens after
    dy2static conversion (``StaticFunction._converted``): the whole
    call runs under ``fleet.recompute`` with this policy, so its
    backward recomputes the non-saveable intermediates instead of
    keeping them live — which is what moves the captured executable's
    ``static_peak_bytes``. Gradients are bitwise-identical either way.
    The wrapped function must be a pure forward (args -> outputs);
    train-step closures that call ``.backward()`` inside should use
    ``Model.prepare(remat=)`` instead, which remats the transformer
    blocks themselves."""
    from ..distributed.fleet.recompute import _POLICIES
    if policy is True or policy == "full":
        return None
    if policy is None or policy not in _POLICIES:
        raise ValueError(
            f"to_static(remat={policy!r}): unknown remat policy; "
            f"expected True, 'full', or one of "
            f"{sorted(k for k in _POLICIES if isinstance(k, str))}")
    return policy


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, remat=None, **kwargs):
    """``paddle.jit.to_static`` analog (reference ``jit/api.py:135``).

    ``remat`` (TPU extension, ISSUE 19): ``True``/'full' or a
    ``fleet.recompute`` policy name runs the converted function under
    selective activation recompute at capture — see
    :func:`_resolve_remat`."""
    def deco(fn):
        if isinstance(fn, StaticFunction):
            if input_spec is not None:
                fn._input_spec = input_spec
            return fn
        import functools
        sf = StaticFunction(fn, build_strategy, backend, full_graph,
                            remat=(_resolve_remat(remat),)
                            if remat else None)
        functools.update_wrapper(sf, fn, updated=[])
        sf._input_spec = input_spec
        return sf

    if function is not None:
        return deco(function)
    return deco


def not_to_static(fn):
    fn._pdtpu_not_to_static = True
    return fn


def ignore_module(modules):
    pass


def enable_to_static(flag):
    pass


class BuildStrategy:
    """Compatibility shim (reference CompiledProgram BuildStrategy); XLA owns
    all the fusion/inlining decisions these flags used to toggle."""

    def __init__(self):
        self.build_cinn_pass = False
        self.enable_inplace = True


# --- save / load (inference program export) --------------------------------
# ``paddle.jit.save`` analog (reference ``jit/api.py:744`` -> TranslatedLayer
# ``:1246``): the traced program is exported as serialized StableHLO via
# jax.export (the TPU-native ProgramDesc: SURVEY §7 maps ProgramDesc/PIR to
# StableHLO as the IR). Format:
#   {path}.pdmodel   pickle {stablehlo: bytes, param_names, out_struct, ...}
#   {path}.pdiparams the parameter/buffer state dict (framework.save format)
# ``jit.load`` rebuilds a TranslatedLayer that executes the program without
# the original Python class.

class _ExportTracker:
    """Substitutes traced values for the captured parameter tensors during
    program export; state writes are swallowed (the exported program is a
    pure inference function)."""

    def __init__(self, mapping):
        self.map = mapping
        self.env: dict[int, Any] = {}

    def on_create(self, t):
        pass

    def on_read(self, t):
        tid = id(t)
        if tid in self.map:
            return self.map[tid]
        if tid in self.env:
            return self.env[tid]
        return t._data

    def on_write(self, t, val):
        self.env[id(t)] = val

    def on_grad_write(self, t):
        pass

    def add_host_sync(self, fn):
        pass


def _encode_structure(out):
    """Picklable descriptor of the output pytree; Tensors become indices."""
    counter = [0]

    def enc(o):
        if isinstance(o, Tensor):
            i = counter[0]
            counter[0] += 1
            return ("t", i)
        if isinstance(o, (list, tuple)):
            return ("seq", type(o).__name__, [enc(x) for x in o])
        if isinstance(o, dict):
            # tensor indices MUST follow _flatten_tensors' walk order,
            # which visits dict keys sorted — insertion order here would
            # silently swap values between keys
            return ("d", {k: enc(o[k]) for k in sorted(o)})
        return ("c", o)
    return enc(out), counter[0]


def _decode_structure(desc, tensors):
    kind = desc[0]
    if kind == "t":
        return tensors[desc[1]]
    if kind == "seq":
        seq = [_decode_structure(x, tensors) for x in desc[2]]
        return tuple(seq) if desc[1] == "tuple" else seq
    if kind == "d":
        return {k: _decode_structure(v, tensors) for k, v in desc[1].items()}
    return desc[1]


def _spec_avals(specs):
    """InputSpecs -> jax avals; None dims become symbolic dimensions (one
    shared symbol per position index so equal batch dims stay equal)."""
    from jax import export as jexport
    has_dynamic = any(d is None for s in specs for d in s.shape)
    if not has_dynamic:
        return [jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype))
                for s in specs], False
    scope = jexport.SymbolicScope()
    avals = []
    for si, s in enumerate(specs):
        parts = []
        for di, d in enumerate(s.shape):
            parts.append(f"d{di}" if d is None else str(d))
        shape = jexport.symbolic_shape(",".join(parts) or "", scope=scope)
        avals.append(jax.ShapeDtypeStruct(shape, jnp.dtype(s.dtype)))
    return avals, True


def _resolve_input_spec(fn_or_layer, input_spec):
    from ..static import InputSpec
    if input_spec is None:
        target = fn_or_layer
        from ..nn import Layer
        if isinstance(fn_or_layer, Layer):
            target = getattr(type(fn_or_layer).forward, "__wrapped__",
                             fn_or_layer.forward)
        input_spec = getattr(target, "_input_spec", None)
    if input_spec is None:
        raise ValueError(
            "jit.save needs an input_spec: pass input_spec=[InputSpec(...)]"
            " to jit.save or to @to_static")
    specs = []
    for s in input_spec:
        if isinstance(s, InputSpec):
            specs.append(s)
        elif isinstance(s, Tensor):
            specs.append(InputSpec.from_tensor(s))
        else:
            raise TypeError(f"input_spec entries must be InputSpec/Tensor, "
                            f"got {type(s).__name__}")
    return specs


def save(layer, path, input_spec=None, **config):
    """Export ``layer`` (or a ``@to_static`` function) as a standalone
    inference program + parameters (reference ``jit/api.py:744``)."""
    import pickle

    from .. import framework as fw
    from ..core.autograd import no_grad
    from ..nn import Layer
    from jax import export as jexport

    specs = _resolve_input_spec(layer, input_spec)

    if isinstance(layer, Layer):
        named = layer.state_dict()
        fn = layer
    else:
        fn = layer.fn if isinstance(layer, StaticFunction) else layer
        if not callable(fn):
            raise TypeError("jit.save expects a Layer or a callable")
        # discover captured state with a probe run on example inputs
        d = _DiscoveryTracker()
        ex_args = [Tensor(jnp.asarray(s._example())) for s in specs]
        old = tensor_mod.set_tracker(d)
        try:
            with no_grad():
                fn(*ex_args)
        finally:
            tensor_mod.set_tracker(old)
        named = {f"var_{i}": t for i, t in enumerate(
            t for t in d.inputs if not any(t is a for a in ex_args))}

    names = list(named)
    ptensors = [named[n] for n in names]

    def pure(param_vals, *input_vals):
        tr = _ExportTracker(
            {id(t): v for t, v in zip(ptensors, param_vals)})
        old = tensor_mod.set_tracker(tr)
        try:
            with no_grad():
                out = fn(*[Tensor(v) for v in input_vals])
        finally:
            tensor_mod.set_tracker(old)
        flat = _flatten_tensors(out, [])
        return [tr.env.get(id(t), t._data) for t in flat], out

    def pure_vals(param_vals, *input_vals):
        return pure(param_vals, *input_vals)[0]

    param_vals = [t._read() for t in ptensors]
    avals, symbolic = _spec_avals(specs)
    param_avals = [jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for v in param_vals]
    try:
        exported = jexport.export(jax.jit(pure_vals))(param_avals, *avals)
    except Exception:
        if not symbolic:
            raise
        # model not shape-polymorphic (static reshapes etc.): fall back to
        # the example's concrete shapes
        warnings.warn("jit.save: symbolic-shape export failed; exporting "
                      "with concrete example shapes instead")
        avals = [jax.ShapeDtypeStruct(
            tuple(2 if d is None else d for d in s.shape),
            jnp.dtype(s.dtype)) for s in specs]
        exported = jexport.export(jax.jit(pure_vals))(param_avals, *avals)

    # run once concretely to learn the output structure
    with no_grad():
        _, out_example = pure(param_vals,
                              *[jnp.zeros([2 if d is None else d
                                           for d in s.shape],
                                          jnp.dtype(s.dtype))
                                for s in specs])
    out_struct, n_out = _encode_structure(out_example)

    # output names for the inference Predictor (reference: fetch-var
    # names in the saved program): explicit ``output_names=[...]`` wins,
    # else dict keys / tensor .name along the flatten order, else out{i}
    out_names = []

    def _name_walk(o, path):
        if isinstance(o, Tensor):
            nm = getattr(o, "name", None)
            out_names.append(nm if nm else
                             (path or f"out{len(out_names)}"))
        elif isinstance(o, (list, tuple)):
            for i, v in enumerate(o):
                _name_walk(v, f"{path}.{i}" if path else str(i))
        elif isinstance(o, dict):
            for k in sorted(o):
                _name_walk(o[k], f"{path}.{k}" if path else str(k))

    explicit = config.get("output_names")
    if explicit:
        out_names = [str(n) for n in explicit]
    else:
        _name_walk(out_example, "")
        # all-positional fallback keeps the legacy out{i} names
        if all(n.isdigit() for n in out_names):
            out_names = [f"out{i}" for i in range(len(out_names))]
    if len(out_names) != n_out:
        out_names = [f"out{i}" for i in range(n_out)]

    meta = {
        "format": "pdtpu.jit.v1",
        "stablehlo": bytes(exported.serialize()),
        "param_names": names,
        "out_struct": out_struct,
        "n_out": n_out,
        "in_specs": [(s.shape, s.dtype, s.name) for s in specs],
        "out_names": out_names,
    }
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump(meta, f, protocol=4)
    fw.save(dict(zip(names, ptensors)), path + ".pdiparams")


class TranslatedLayer:
    """A loaded inference program (reference TranslatedLayer,
    ``jit/api.py:1246``): callable without the original model code."""

    def __init__(self, meta, params):
        # slim metadata for consumers (inference.Predictor IO names) —
        # everything except the serialized program, which would pin
        # potentially hundreds of MB alongside the deserialized Exported
        self._meta = {k: v for k, v in meta.items() if k != "stablehlo"}
        from jax import export as jexport
        self._exported = jexport.deserialize(bytearray(meta["stablehlo"]))
        self._names = meta["param_names"]
        self._out_struct = meta["out_struct"]
        self._params = params
        self._call = jax.jit(
            lambda pv, *xs: self._exported.call(pv, *xs))

    def __call__(self, *inputs):
        return self.forward(*inputs)

    def forward(self, *inputs):
        vals = [x._read() if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        pv = [self._params[n]._read() for n in self._names]
        outs = self._call(pv, *vals)
        tensors = [Tensor(o, stop_gradient=True) for o in outs]
        return _decode_structure(self._out_struct, tensors)

    def state_dict(self):
        return dict(self._params)

    def set_state_dict(self, sd):
        for k, v in sd.items():
            if k in self._params:
                self._params[k]._data = (v._read() if isinstance(v, Tensor)
                                         else jnp.asarray(v))

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is an inference program; "
                           "training requires the original model code")


def load(path, **config):
    """Load a ``jit.save``d program as a TranslatedLayer (reference
    ``jit/api.py:1246``)."""
    import pickle

    from .. import framework as fw
    with open(path + ".pdmodel", "rb") as f:
        meta = pickle.load(f)
    if meta.get("format") != "pdtpu.jit.v1":
        raise ValueError(f"{path}.pdmodel is not a pdtpu jit export")
    params = fw.load(path + ".pdiparams")
    return TranslatedLayer(meta, params)


from .multi_step import WindowRunner, multi_step  # noqa: E402,F401
