"""Stall watchdog: a daemon-thread heartbeat monitor with flight
capture (ISSUE 14 tentpole, part 2).

A hung dispatch is the one failure the rest of the observability stack
cannot see: no event fires, no metric moves, the caller just never
returns — and a device that has stopped answering looks exactly
like a long compile.  The watchdog turns silence into evidence:

* Callers :func:`arm` an operation with a deadline (engine dispatches,
  ``DisaggServer`` handoffs, rpc invokes, ``Model.fit`` steps — the
  arm/heartbeat marks sit at the EXISTING event-emission sites, so
  ``PDTPU_METRICS=off`` keeps today's behavior bitwise: :func:`arm`
  returns a no-op token).  Long-lived operations (a fit) refresh the
  deadline with ``token.heartbeat()`` each step; bounded ones (a
  dispatch) just ``disarm()`` on completion — a clean run leaves
  nothing armed and dumps nothing.
* A daemon thread polls (``watchdog_poll_ms`` flag).  Past the
  deadline it captures EVERY thread's stack (``sys._current_frames``
  — the in-process capture; a best-effort ``faulthandler`` dump lands
  next to the record as ``*.stacks.txt`` for the raw-fd view), emits
  ``watchdog.stall`` into the event ring, dumps the flight record
  (stacks + the victim's full lifecycle timeline) and exports the
  Chrome trace alongside it (``*.trace.json``).
* When the armer asked for an interrupt (the serving engine does), the
  stalled thread gets a coded exception injected via
  ``PyThreadState_SetAsyncExc`` —
  :class:`~paddle_tpu.core.errors.EngineStallError` (PDT-E020)
  surfaces from ``engine.step()`` instead of tier-1 hanging forever.
  The injection lands at the next bytecode boundary, so it recovers
  Python-level stalls (spin loops, lock waits with timeouts, the
  ``engine_stall`` drill); a thread truly wedged inside a C call can
  only be stack-dumped, not recovered — the flight record is still
  written either way.

Deadlines come from the ``watchdog_stall_ms`` flag (0 = off; the
engine's ``watchdog_ms`` kwarg overrides per instance).  Detection
latency is deadline + one poll interval.  Size deadlines above the
worst case of the operation INCLUDING first compiles: an interrupt
that lands mid-compile aborts a compile that would have been cached,
so the next attempt recompiles and stalls again — a deadline-induced
livelock, not a hang the watchdog can fix.
"""
from __future__ import annotations

import ctypes
import sys
import threading
import time
import traceback

from ..core import state as _state
from . import events as _events
from . import metrics as _metrics

__all__ = ["arm", "arm_collective", "armed", "thread_stacks",
           "Watchdog", "NULL_TOKEN"]


def thread_stacks() -> dict:
    """Every live thread's current stack as ``{"name:ident": text}`` —
    the JSON-embeddable capture a flight record can carry (what
    ``faulthandler.dump_traceback`` prints, readable in-process)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        out[f"{names.get(ident, '?')}:{ident}"] = "".join(
            traceback.format_stack(frame))
    return out


def _async_raise(thread_id, exc_type) -> bool:
    """Inject ``exc_type`` into ``thread_id`` at its next bytecode
    boundary (CPython ``PyThreadState_SetAsyncExc``)."""
    res = ctypes.pythonapi.PyThreadState_SetAsyncExc(
        ctypes.c_ulong(thread_id), ctypes.py_object(exc_type))
    if res > 1:
        # invalid state: undo rather than poison an unknown thread
        ctypes.pythonapi.PyThreadState_SetAsyncExc(
            ctypes.c_ulong(thread_id), None)
        return False
    return res == 1


class _NullToken:
    """The disarmed token: every watchdog call site can hold one
    unconditionally, so metrics-off / deadline-0 costs one attribute
    call and no state."""

    __slots__ = ()
    fired = False
    dump_path = None

    def heartbeat(self):
        pass

    def disarm(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_TOKEN = _NullToken()


class _Entry:
    __slots__ = ("site", "key", "deadline_ms", "deadline", "thread_id",
                 "interrupt_exc", "extra", "fired", "dump_path",
                 "disarmed")

    def __init__(self, site, key, deadline_ms, thread_id, interrupt_exc,
                 extra):
        self.site = str(site)
        self.key = str(key)
        self.deadline_ms = float(deadline_ms)
        self.deadline = time.monotonic() + self.deadline_ms / 1e3
        self.thread_id = thread_id
        self.interrupt_exc = interrupt_exc
        self.extra = extra
        self.fired = False
        self.dump_path = None
        self.disarmed = False


class _Token:
    __slots__ = ("_wd", "_entry")

    def __init__(self, wd, entry):
        self._wd = wd
        self._entry = entry

    @property
    def fired(self):
        return self._entry.fired

    @property
    def dump_path(self):
        return self._entry.dump_path

    def heartbeat(self):
        """Refresh the deadline (one mark per completed unit of work —
        e.g. per train step); also re-arms after a fire, so a slow
        phase that recovers keeps being monitored."""
        self._wd._heartbeat(self._entry)

    def disarm(self):
        self._wd._disarm(self._entry)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.disarm()
        return False


class Watchdog:
    """The monitor: armed entries + one lazy daemon poll thread."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: list[_Entry] = []
        self._thread = None

    # ------------------------------------------------------------ API --
    def arm(self, site, deadline_ms, *, key="", interrupt_exc=None,
            thread_id=None, extra=None):
        """Monitor one operation; returns a token (``heartbeat`` /
        ``disarm`` / context manager).  A no-op token when the deadline
        is unset or metrics are off — arming must never change
        metrics-off behavior."""
        ms = float(deadline_ms or 0.0)
        if ms <= 0 or not _metrics.enabled():
            return NULL_TOKEN
        entry = _Entry(site, key, ms,
                       threading.get_ident() if thread_id is None
                       else thread_id,
                       interrupt_exc, extra)
        with self._lock:
            self._entries.append(entry)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._loop, name="pdtpu-watchdog",
                    daemon=True)
                self._thread.start()
        return _Token(self, entry)

    def armed(self) -> list:
        """``[(site, key), ...]`` of live (non-disarmed) entries — the
        clean-run assertion surface."""
        with self._lock:
            return [(e.site, e.key) for e in self._entries
                    if not e.disarmed]

    # ------------------------------------------------------ internals --
    def _heartbeat(self, entry):
        with self._lock:
            entry.deadline = time.monotonic() + entry.deadline_ms / 1e3
            entry.fired = False

    def _disarm(self, entry):
        with self._lock:
            entry.disarmed = True
            try:
                self._entries.remove(entry)
            except ValueError:
                pass
        # the fire/complete race: if the watchdog fired but its
        # injection has not been DELIVERED yet (async exceptions land
        # at bytecode boundaries), a disarm on the target thread means
        # the operation finished — clear the pending injection so it
        # cannot surface in unrelated code after this point
        if entry.fired and entry.interrupt_exc is not None \
                and entry.thread_id == threading.get_ident():
            ctypes.pythonapi.PyThreadState_SetAsyncExc(
                ctypes.c_ulong(entry.thread_id), None)

    def _poll_s(self) -> float:
        try:
            return max(float(_state.get_flag("watchdog_poll_ms")),
                       1.0) / 1e3
        except Exception:
            return 0.02

    def _loop(self):
        while True:
            time.sleep(self._poll_s())
            now = time.monotonic()
            with self._lock:
                if not self._entries:
                    # idle: exit instead of polling forever — arm()
                    # sees _thread is None (set under this lock) and
                    # restarts the loop with the next entry
                    self._thread = None
                    return
                due = [e for e in self._entries
                       if not e.fired and not e.disarmed
                       and now > e.deadline]
                for e in due:
                    e.fired = True
            for e in due:
                try:
                    self._fire(e)
                except Exception:
                    pass     # the monitor must never take down the host

    def _fire(self, entry):
        """One stall: stacks -> ring event -> interrupt -> flight dump
        (+ Chrome trace and faulthandler companions).  The interrupt
        goes out BEFORE the dump's file IO and only after re-checking
        the entry under the lock: every millisecond between "deadline
        exceeded" and "exception injected" is a window in which the
        operation could legitimately complete, and an injection landing
        after completion discards a real result (for a donated-buffer
        dispatch, one whose buffers are already consumed).  The
        residual boundary — completion between the locked check and
        the bytecode boundary where CPython delivers the exception —
        is inherent to async injection; ``_disarm`` clears a pending
        undelivered injection on the disarming thread to keep it from
        escaping past the armed region."""
        stacks = thread_stacks()
        _events.emit("watchdog.stall", site=entry.site, key=entry.key,
                     deadline_ms=entry.deadline_ms)
        _metrics.registry().counter(
            "watchdog.stalls", "operations past their stall deadline",
            labels={"site": entry.site}).inc()
        if entry.interrupt_exc is not None:
            with self._lock:
                interrupt = not entry.disarmed
            if interrupt:
                _async_raise(entry.thread_id, entry.interrupt_exc)
        extra = {"site": entry.site, "key": entry.key,
                 "deadline_ms": entry.deadline_ms, "stacks": stacks}
        if entry.extra:
            extra.update(entry.extra)
        path = _events.dump("watchdog_stall", extra=extra)
        entry.dump_path = path
        if path and path.endswith(".json"):
            stem = path[:-len(".json")]
            try:
                from . import tracing as _tracing
                _tracing.export_trace(stem + ".trace.json")
            except Exception:
                pass
            try:
                import faulthandler
                with open(stem + ".stacks.txt", "w") as f:
                    faulthandler.dump_traceback(file=f,
                                                all_threads=True)
            except Exception:
                pass


_WD = Watchdog()


def arm(site, deadline_ms, *, key="", interrupt_exc=None,
        thread_id=None, extra=None):
    """Arm the process watchdog (module-level singleton); see
    :meth:`Watchdog.arm`."""
    return _WD.arm(site, deadline_ms, key=key,
                   interrupt_exc=interrupt_exc, thread_id=thread_id,
                   extra=extra)


def armed() -> list:
    """Live armed entries — empty after every clean run."""
    return _WD.armed()


def arm_collective(site, *, key="", deadline_ms=None, extra=None):
    """Arm one collective dispatch against a dead-peer hang (ISSUE 15).

    The deadline defaults to the ``collective_timeout_ms`` flag (0 =
    off -> NULL token, today's behavior bitwise); past it the blocked
    caller gets :class:`~paddle_tpu.core.errors.CollectiveTimeoutError`
    (PDT-E021) injected, after stacks + flight record + Chrome trace
    are captured — a dead rank surfaces as a coded, postmortem-ready
    error instead of hanging every survivor inside the psum.  Armed
    around ``Group.psum_mean``, ``DataParallel.apply_collective_grads``,
    the pipeline forward/train_batch dispatches, and the elastic
    supervisor's store-backed allreduce.  Size the deadline above the
    operation's worst case INCLUDING first compiles (see the module
    docstring's livelock note)."""
    from ..core.errors import CollectiveTimeoutError

    ms = deadline_ms
    if ms is None:
        try:
            ms = float(_state.get_flag("collective_timeout_ms"))
        except Exception:
            ms = 0.0
    return _WD.arm(site, ms, key=key,
                   interrupt_exc=CollectiveTimeoutError, extra=extra)
