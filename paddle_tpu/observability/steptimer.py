"""Training step telemetry: wall-time, retraces, tokens/sec, MFU.

``hapi.Model.fit`` owns a :class:`StepTimer` per fit and calls
:meth:`StepTimer.step` once per completed train step (per-batch path,
windowed path and epoch tails alike); custom loops can do the same.
The timer records into the process-global ``"default"`` registry:

* ``train.step_ms``        — step wall-time histogram (log buckets)
* ``train.steps``          — completed steps counter
* ``train.retraces``       — RE-traces of the compiled train step past
  the first compile (``Executable.trace_count`` deltas): a steady-state
  increment here is the shape/weakref churn regression the jit cache
  guards warn about, surfaced as a counter a dashboard can alert on
* ``train.tokens_per_sec`` — online throughput gauge (EMA-free: last
  completed step's tokens / wall)
* ``train.mfu``            — model-flops-utilization estimate gauge,
  ``6 * n_params * tokens/sec / peak_flops`` (the standard LM
  approximation); 0.0 when the device's peak is unknown (CPU)

``Optimizer.step`` feeds the same registry from its own side:
``train.opt_step_ms`` (eager update wall time) and
``train.fused_bucket_dispatches`` (flat-bucket kernel launches per
fused step — the PR4 O(buckets) claim as a live counter).

The overlap grad-sync scheduler (``distributed/overlap.py``, ISSUE 11)
adds ``train.comm_ms`` (per-bucket collective wall histogram),
``train.overlap_frac`` (fraction of collective time hidden under
backward, last step), ``train.bucket_syncs`` and
``train.overlap_bytes``.

With ``PDTPU_METRICS=off`` every call is a flag check and return.  The
optional one-line log (``metrics_log_every`` flag / ``log_every``
kwarg) goes through the ``paddle_tpu.observability`` logger every N
steps.

The window's timeline (ISSUE 40)
--------------------------------
A user's own loop (a ``jit.to_static`` step called in a ``for``) never
reaches a :class:`StepTimer`, so the program also keeps three
preallocated rings of ``time.perf_counter_ns()`` marks, a few stores a
row, with or without a profiler:

* :func:`call_log` — the last ``CALL_RING`` compiled ``to_static``
  calls (``jit.StaticFunction.__call__``): ``fn`` (the compiled
  program's index into :func:`call_fn_names`), ``n`` (the call's
  number, monotone in the process, and the ``n`` attribute of its
  ``to_static.call`` span) and five marks ``enter``, ``read_state``,
  ``launch``, ``launched``, ``done`` (entry, the clock the three inner
  spans took as they opened, the end): four host phases a call,
  ``lookup``, ``read_state``, ``launch``, ``write_state``.  The eager
  first call and a capture write no row.
* :func:`read_log` — the last ``READ_RING`` blocking host reads of a
  device value (``Tensor.numpy/item/tolist/__array__/__bool__/
  __float__/__int__/__index__``, the ``tensor.readback`` span):
  ``begin``, ``end`` and ``resource.getrusage(RUSAGE_SELF)`` at the
  end (``utime_ns``, ``stime_ns``, ``nivcsw``, ``majflt``): between two
  reads the deltas say whether the host computed, was descheduled or
  paged.
* :func:`gc_log` — the last ``GC_RING`` collector pauses of generation
  2 or of 1 ms and more (``begin``, ``end``, ``generation``,
  ``collected``), from one ``gc.callbacks`` hook, installed as the
  first compiled program is built; a generation-2 pause is also the
  span ``host.gc``.

Each returns a copy in time order as a numpy record array, empty where
``PDTPU_METRICS`` is off (nothing is written then).  While a profiler
session is live the same moments are spans on the device trace's
clock; ``perf/window_log.py`` pairs the two by ``n``.
"""
from __future__ import annotations

import gc
import itertools
import logging
import resource
import struct
import time

import numpy as np

from . import metrics as _metrics
from . import tracing as _tracing
from .metrics import LATENCY_BUCKETS_MS, enabled

__all__ = ["StepTimer", "device_peak_flops", "note_optimizer_step",
           "call_log", "read_log", "gc_log", "call_fn_names"]

_log = logging.getLogger("paddle_tpu.observability")

# bf16 peak TFLOP/s by TPU device kind (vendor specs) — the MFU
# denominator; None (CPU / unknown) leaves the mfu gauge at 0.0
_PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def device_peak_flops():
    """Peak FLOP/s of device 0 (None when unknown, e.g. CPU)."""
    try:
        import jax
        kind = str(getattr(jax.devices()[0], "device_kind", ""))
    except Exception:
        return None
    for k, v in _PEAK_TFLOPS.items():
        if k.lower() in kind.lower():
            return v * 1e12
    return None


class StepTimer:
    def __init__(self, registry=None, prefix="train", n_params=None,
                 peak_flops=None, log_every=None):
        from ..core import state as _state
        reg = registry or _metrics.registry()
        self.n_params = int(n_params) if n_params else None
        self.peak_flops = (device_peak_flops() if peak_flops is None
                           else peak_flops)
        self.log_every = int(_state.get_flag("metrics_log_every")
                             if log_every is None else log_every)
        self._h_step = reg.histogram(
            prefix + ".step_ms", "train step wall time",
            LATENCY_BUCKETS_MS)
        self._c_steps = reg.counter(prefix + ".steps",
                                    "completed train steps")
        self._c_retrace = reg.counter(
            prefix + ".retraces",
            "compiled-train-step re-traces past the first compile")
        self._g_tps = reg.gauge(prefix + ".tokens_per_sec",
                                "tokens consumed per second (online)")
        self._g_mfu = reg.gauge(
            prefix + ".mfu", "model-flops-utilization estimate "
            "(6*N*tokens/sec over device peak)")
        self._t = None
        self._base_traces = None
        self._seen = 0

    def mark(self):
        """(Re)arm the step clock without recording — call after a
        pause (eval pass, checkpoint) so the gap isn't a 'step'."""
        self._t = time.perf_counter() if enabled() else None

    def step(self, tokens=None, trace_count=None):
        """One completed train step. ``tokens``: tokens this step
        consumed (throughput/MFU gauges); ``trace_count``: current
        total ``Executable.trace_count`` of the compiled step."""
        if not enabled():
            self._t = None
            return
        now = time.perf_counter()
        if self._t is not None:
            dt = now - self._t
            self._h_step.observe(dt * 1e3)
            self._c_steps.inc()
            self._seen += 1
            if tokens and dt > 0:
                tps = float(tokens) / dt
                self._g_tps.set(round(tps, 1))
                if self.peak_flops and self.n_params:
                    self._g_mfu.set(round(
                        6.0 * self.n_params * tps / self.peak_flops, 4))
        if trace_count is not None:
            if self._base_traces is None:
                # the first observation is the compile itself, not a
                # regression — count deltas from here
                self._base_traces = int(trace_count)
            elif trace_count > self._base_traces:
                self._c_retrace.inc(int(trace_count) - self._base_traces)
                self._base_traces = int(trace_count)
        if self.log_every and self._seen \
                and self._seen % self.log_every == 0:
            _log.info(
                "step %d: %.2f ms/step (mean), %.1f tok/s, mfu %.3f, "
                "retraces %d", self._seen, self._h_step.mean,
                float(self._g_tps.value or 0.0),
                float(self._g_mfu.value or 0.0), self._c_retrace.value)
        self._t = now


# cached metric handles for the optimizer-side hook (one-time lookups)
_opt_hist = None
_bucket_counter = None


def note_optimizer_step(wall_ms, fused_buckets=0):
    """Record one eager optimizer update: wall time histogram plus the
    fused flat-bucket dispatch count (0 = per-param path)."""
    global _opt_hist, _bucket_counter
    if not enabled():
        return
    if _opt_hist is None:
        reg = _metrics.registry()
        _opt_hist = reg.histogram(
            "train.opt_step_ms", "eager optimizer.step wall time",
            LATENCY_BUCKETS_MS)
        _bucket_counter = reg.counter(
            "train.fused_bucket_dispatches",
            "fused flat-bucket update kernels launched")
    _opt_hist.observe(float(wall_ms))
    if fused_buckets:
        _bucket_counter.inc(int(fused_buckets))


# ---------------------------------------------------------------------
# the window's timeline: three rings of perf_counter_ns marks
# ---------------------------------------------------------------------
CALL_RING, READ_RING, GC_RING = 4096, 4096, 1024
CALL_FIELDS = ("n", "fn", "enter", "read_state", "launch", "launched",
               "done")
READ_FIELDS = ("seq", "begin", "end", "utime_ns", "stime_ns", "nivcsw",
               "majflt")
GC_FIELDS = ("seq", "begin", "end", "generation", "collected")
# a young collection is logged only where it paused the host this long
GC_LOG_NS = 1_000_000


class _Ring:
    """The last ``size`` rows of int64s in one preallocated buffer.
    The first field is the row's number, from 1 up (``next(numbers)``):
    row ``k`` lies in slot ``(k - 1) % size``, and a slot never written
    reads as number 0.  A row is stored by one ``pack_into``, whole or
    not at all."""

    __slots__ = ("dtype", "size", "stride", "pack", "buf", "numbers")

    def __init__(self, size, fields):
        self.dtype = np.dtype([(f, "<i8") for f in fields])
        self.size, self.stride = size, 8 * len(fields)
        self.pack = struct.Struct(f"<{len(fields)}q").pack_into
        self.clear()

    def clear(self):
        self.buf = bytearray(self.size * self.stride)
        self.numbers = itertools.count(1)

    def put(self, k, *values):
        self.pack(self.buf, ((k - 1) % self.size) * self.stride, k, *values)

    def rows(self):
        """The rows held, oldest first, as a record array (a copy);
        none while ``PDTPU_METRICS`` is off."""
        rows = np.frombuffer(self.buf, dtype=self.dtype)
        number = rows[self.dtype.names[0]]
        keep = (number > 0) if enabled() else np.zeros(0, dtype=int)
        return np.sort(rows[keep], order=self.dtype.names[0])


# ``jit._Executable.__call__`` numbers and stores its own row in
# ``_calls`` (under ``enabled()``, read once a call)
_calls = _Ring(CALL_RING, CALL_FIELDS)
_reads = _Ring(READ_RING, READ_FIELDS)
_gcs = _Ring(GC_RING, GC_FIELDS)
_fn_names: list = []


def register_fn(name) -> int:
    """A compiled program is built (``jit._Executable``): its rows'
    ``fn``, an index of its own into :func:`call_fn_names`, so that two
    programs of one name (every ``to_static(layer)`` is ``forward``)
    keep apart.  The first one installs the collector's hook."""
    if not _fn_names:
        gc.callbacks.append(_gc_hook)
    _fn_names.append(str(name))
    return len(_fn_names) - 1


def call_fn_names() -> list:
    """The names that a call row's ``fn`` indexes, a compiled program
    each."""
    return list(_fn_names)


def note_read(begin, end):
    """One blocking host read of a device value (``core/tensor.py``):
    its wait, and the process's CPU seconds, involuntary context
    switches and major page faults as the read ends."""
    if not enabled():
        return
    ru = resource.getrusage(resource.RUSAGE_SELF)
    _reads.put(next(_reads.numbers), begin, end, int(ru.ru_utime * 1e9),
               int(ru.ru_stime * 1e9), ru.ru_nivcsw, ru.ru_majflt)


# the collector's hook: the pause that has begun, and the generation-2
# span that is open
_gc_t0 = 0
_gc_span = None


def _gc_hook(phase, info):
    """``gc.callbacks``: every collection costs two clock reads and a
    compare; a generation-2 one is also the span ``host.gc``, and it or
    any pause of ``GC_LOG_NS`` is a row of the third ring."""
    global _gc_t0, _gc_span
    if phase == "start":
        if not enabled():
            _gc_t0 = 0
            return
        if info["generation"] == 2:
            _gc_span = _tracing.span("host.gc", generation=2)
            _gc_span.__enter__()
        _gc_t0 = time.perf_counter_ns()
        return
    t0, t1 = _gc_t0, time.perf_counter_ns()
    generation = info["generation"]
    if not t0 or (generation != 2 and t1 - t0 < GC_LOG_NS):
        return
    if _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None
    _gcs.put(next(_gcs.numbers), t0, t1, generation, info["collected"])


def _reset_logs():
    """Test hook: empty rings, numbers from 1 (the programs' names and
    the collector's hook stay)."""
    for ring in (_calls, _reads, _gcs):
        ring.clear()


def call_log():
    """The last ``CALL_RING`` compiled calls, oldest first."""
    return _calls.rows()


def read_log():
    """The last ``READ_RING`` blocking host reads, oldest first."""
    return _reads.rows()


def gc_log():
    """The last ``GC_RING`` logged collector pauses, oldest first."""
    return _gcs.rows()
