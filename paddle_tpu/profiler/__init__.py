"""``paddle.profiler`` parity — scheduled profiling with chrome-trace export.

Capability analog of SURVEY C29 + the Python profiler API
(``python/paddle/profiler/profiler.py:346`` Profiler,
``utils.py`` RecordEvent, ``profiler_statistic.py`` summaries,
``chrometracing_logger.cc`` export). TPU-native split:

- HOST tracing is framework-owned: the program's spans
  (``observability.tracing.span``, of which ``RecordEvent`` is a thin
  caller) + automatic per-op dispatch events (a hook in
  ``core.dispatch``) land in a process-local buffer exported as chrome ``trace.json`` (load in
  ``chrome://tracing`` / Perfetto — same workflow as the reference).
- DEVICE tracing delegates to ``jax.profiler`` (XLA's tracer): when a
  device target is enabled the Profiler brackets the record window with
  ``jax.profiler.start_trace/stop_trace``, producing TensorBoard/Perfetto
  traces with per-HLO timing — the CUPTI analog on TPU.
- The wait/warmup/active scheduling model (``make_scheduler``,
  ``export_chrome_tracing``) matches the reference API.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum
from typing import Callable, Iterable, Optional

from ..core import dispatch as _dispatch
from ..observability import tracing as _tracing


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1          # accepted for API parity; maps to the device tracer
    CUSTOM_DEVICE = 2
    TPU = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(*, closed: int = 0, ready: int = 0, record: int = 1,
                   repeat: int = 0, skip_first: int = 0) -> Callable:
    """Reference ``profiler.py make_scheduler``: per-step state machine
    skip_first -> [closed -> ready -> record...] cycles."""
    period = closed + ready + record
    if record <= 0:
        raise ValueError("record span must be positive")

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        cycle = s // period
        if repeat and cycle >= repeat:
            return ProfilerState.CLOSED
        off = s % period
        if off < closed:
            return ProfilerState.CLOSED
        if off < closed + ready:
            return ProfilerState.READY
        if off == period - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """Reference ``profiler.py export_chrome_tracing`` handler."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof: "Profiler"):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time() * 1000)}"
                      f".paddle_trace.json")
        prof.export(path)

    return handler


class _HostEventBuffer:
    def __init__(self):
        self.events: list = []
        self.lock = threading.Lock()

    def add(self, name, ts, dur, tid, cat):
        with self.lock:
            self.events.append((name, ts, dur, tid, cat))

    def clear(self):
        with self.lock:
            self.events = []


_buffer = _HostEventBuffer()
_active_profiler: Optional["Profiler"] = None


class RecordEvent:
    """User-scope span (reference ``profiler/utils.py RecordEvent``): a
    thin caller of the program's one span implementation
    (``observability.tracing``), so it shows up inside device traces
    (a ``jax.profiler`` annotation), in this Profiler's host buffer
    while a record window is open, and in the event ring as one
    ``span`` record."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._span = None

    def begin(self):
        self._span = _tracing._UserSpan(self.name)
        self._span.__enter__()

    def end(self):
        if self._span is None:
            return
        span, self._span = self._span, None
        span.__exit__(None, None, None)

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def _span_sink(name, t0_ns, dur_us, cat):
    _buffer.add(name, t0_ns // 1000, dur_us, threading.get_ident(), cat)


def _op_profile_hook(name: str, t0_ns: int, t1_ns: int):
    dur_us = max((t1_ns - t0_ns) // 1000, 1)
    _buffer.add(name, t0_ns // 1000, dur_us,
                threading.get_ident(), "op")
    # per-op dispatch names feed the observability ring while a record
    # window is open — a flight record dumped during profiling shows
    # the exact dispatch sequence leading up to the failure
    from ..observability import events as _obs_events
    _obs_events.emit("op", name=name, dur_us=int(dur_us))


class Profiler:
    """Reference ``profiler.py:346``. Usage matches the reference:

        with profiler.Profiler(targets=[ProfilerTarget.CPU],
                               scheduler=(2, 5)) as p:
            for batch in loader:
                train_step(batch)
                p.step()
        p.summary()
    """

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 timer_only: bool = False, record_shapes: bool = False,
                 profile_memory: bool = False, with_flops: bool = False):
        self.targets = list(targets) if targets else [ProfilerTarget.CPU]
        if scheduler is None:
            self.scheduler = _default_state_scheduler
        elif isinstance(scheduler, tuple):
            start, end = scheduler
            self.scheduler = make_scheduler(closed=max(start, 0), ready=0,
                                            record=end - start, repeat=1,
                                            skip_first=0)
        else:
            self.scheduler = scheduler
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._recording = False
        self._device_tracing = False
        self._trace_dir = None
        self._step_times: list = []
        self._t_step = None

    # -- lifecycle -----------------------------------------------------
    def start(self):
        global _active_profiler
        _active_profiler = self
        self.current_state = self.scheduler(self.step_num)
        self._apply_state()
        self._t_step = time.perf_counter()
        return self

    def stop(self):
        global _active_profiler
        try:
            if self._recording:
                self._stop_record()
                if self.on_trace_ready is not None:
                    self.on_trace_ready(self)
        finally:
            # a raising on_trace_ready handler must not leave the
            # profiler registered as active (the hook is already down:
            # _stop_record runs first and is unconditional)
            _active_profiler = None
            self.current_state = ProfilerState.CLOSED

    def step(self, num_samples: Optional[int] = None):
        now = time.perf_counter()
        if self._t_step is not None:
            self._step_times.append((now - self._t_step, num_samples))
        self._t_step = now
        prev = self.current_state
        self.step_num += 1
        self.current_state = self.scheduler(self.step_num)
        if prev == ProfilerState.RECORD_AND_RETURN or (
                self._recording and
                self.current_state in (ProfilerState.CLOSED,
                                       ProfilerState.READY)):
            try:
                self._stop_record()
                if self.on_trace_ready is not None:
                    self.on_trace_ready(self)
            except BaseException:
                # fail safe: a raising trace handler leaves the bracket
                # DOWN (hook cleared, device tracer stopped) instead of
                # re-arming a window the caller will never close
                self.current_state = ProfilerState.CLOSED
                raise
        self._apply_state()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- internals -----------------------------------------------------
    def _apply_state(self):
        if self.current_state in (ProfilerState.RECORD,
                                  ProfilerState.RECORD_AND_RETURN):
            if not self._recording:
                self._start_record()

    def _start_record(self):
        """Open a record window. Exception-safe bracket (ISSUE 8
        satellite): if anything raises mid-open — including a
        BaseException out of ``jax.profiler.start_trace`` that the
        Exception net below doesn't catch — the half-opened window is
        torn down before the error propagates, so the global dispatch
        hook and the device tracer can never outlive a failed start."""
        self._recording = True
        try:
            _tracing._host_sink = _span_sink
            if not self.timer_only:
                _dispatch._profile_hook = _op_profile_hook
            if any(t in (ProfilerTarget.GPU, ProfilerTarget.TPU,
                         ProfilerTarget.CUSTOM_DEVICE)
                   for t in self.targets):
                try:
                    import jax
                    self._trace_dir = os.environ.get(
                        "PDTPU_PROFILE_DIR", "/tmp/paddle_tpu_profile")
                    jax.profiler.start_trace(self._trace_dir)
                    self._device_tracing = True
                except Exception:
                    self._device_tracing = False
        except BaseException:
            self._stop_record()
            raise

    def _stop_record(self):
        """Close the record window. The global hook comes down FIRST
        and unconditionally — a raising step inside a RECORD window
        exits through here (``__exit__`` -> ``stop``), and the one
        unrecoverable outcome would be the hook surviving to poison
        every later dispatch; ``jax.profiler.stop_trace`` runs under
        its own net for the same reason."""
        self._recording = False
        _dispatch._profile_hook = None
        _tracing._host_sink = None
        if self._device_tracing:
            self._device_tracing = False
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass

    # -- output --------------------------------------------------------
    def export(self, path: str, format: str = "json"):
        """Write collected host events as a chrome trace."""
        events = []
        pid = os.getpid()
        with _buffer.lock:
            snap = list(_buffer.events)
        for name, ts, dur, tid, cat in snap:
            events.append({"ph": "X", "name": name, "cat": cat,
                           "pid": pid, "tid": tid, "ts": ts, "dur": dur})
        with open(path, "w") as f:
            json.dump({"traceEvents": events,
                       "displayTimeUnit": "ms"}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregate host spans by name (the profiler_statistic analog).
        Returns the formatted table and prints it (reference behavior)."""
        agg: dict = {}
        with _buffer.lock:
            snap = list(_buffer.events)
        for name, ts, dur, tid, cat in snap:
            st = agg.setdefault(name, [0, 0, float("inf"), 0.0])
            st[0] += 1
            st[1] += dur
            st[2] = min(st[2], dur)
            st[3] = max(st[3], dur)
        scale = {"s": 1e6, "ms": 1e3, "us": 1.0}[time_unit]
        rows = sorted(agg.items(), key=lambda kv: -kv[1][1])
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(' + time_unit + ')':>14}"
                 f"{'Avg':>10}{'Min':>10}{'Max':>10}"]
        lines.append("-" * len(lines[0]))
        for name, (cnt, tot, mn, mx) in rows:
            lines.append(
                f"{name[:39]:<40}{cnt:>8}{tot / scale:>14.3f}"
                f"{tot / cnt / scale:>10.3f}{mn / scale:>10.3f}"
                f"{mx / scale:>10.3f}")
        table = "\n".join(lines)
        print(table)
        return table

    def benchmark(self):
        """Throughput info from step() timings (reference Timer analog)."""
        if not self._step_times:
            return {}
        times = [t for t, _ in self._step_times]
        samples = [s for _, s in self._step_times if s]
        out = {"steps": len(times),
               "avg_step_time": sum(times) / len(times),
               "min_step_time": min(times),
               "max_step_time": max(times)}
        if samples and len(samples) == len(times):
            out["ips"] = sum(samples) / sum(times)
        return out

    def reset(self):
        _buffer.clear()
        self._step_times = []


def load_profiler_result(filename: str):
    """Reference ``profiler.py load_profiler_result``."""
    with open(filename) as f:
        return json.load(f)


__all__ = [
    "Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
]
