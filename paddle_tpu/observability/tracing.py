"""Distributed tracing: spans over the event ring + Perfetto export.

The distributed half of the observability runtime (ISSUE 12, tentpole
part 1).  PR 8 gave every process an event ring; this module gives the
ring *structure*: a :func:`span` context manager (and :func:`traced`
decorator) writes ``span.begin``/``span.end`` pairs carrying a
propagatable trace context — ``trace_id`` names one logical operation
end-to-end, ``span_id``/``parent_id`` nest the work inside it — and
:func:`export_trace` renders the whole ring (spans, serving lifecycle
events, fault/guard/retry events, profiler ops) as Chrome/Perfetto
trace-event JSON, one track per rank / thread / engine slot.

Context propagation
-------------------
The context is thread-local.  :func:`inject` captures it as a plain
dict; :func:`attach` re-establishes it in another thread/process so
spans opened there become children of the remote caller's span.
``distributed/rpc`` propagates automatically: ``rpc_sync``/``rpc_async``
wrap the outgoing callable in :class:`RemoteTraceContext` (picklable,
rides the existing ``(fn, args, kwargs)`` wire frame unchanged), and
the serving engine stamps the active context onto its
``serving.dispatch`` events — so a trace started at an admission
front-end survives the hop to a prefill worker and into the dispatch
that served it.

Gating
------
There is one span API and no knob of its own.  Every :func:`span`
opens a ``jax.profiler.TraceAnnotation``, which records only while a
profiler session is live (``jax.profiler.start_trace`` or
``profiler.Profiler(targets=[TPU])``): the program's spans then lie in
the ``.xplane.pb`` on the device's clock.  The event ring is gated on
the ``PDTPU_METRICS`` flag: with it off ``span()`` emits nothing,
``inject`` returns ``None``, rpc payloads go out UNWRAPPED (bitwise
pre-observability wire behavior) and ``export_trace`` writes nothing.
"Tracing off" is no session and the flag off; a span then costs the
TraceMe's enter and exit (some 0.3-0.5 us).  ``profiler.RecordEvent``
is a thin caller of the same code.

Event kinds (see the package docstring for the full schema)::

    span.begin   name, span_id, parent_id?, trace_id, tname, ...attrs
    span.end     name, span_id, trace_id, dur_us, error?
    compile.retrace  fn, count, cause        (jit._Executable)

Export format
-------------
:func:`render_trace` returns the Chrome trace-event dict
(``{"traceEvents": [...], "displayTimeUnit": "ms"}``); timestamps are
microseconds relative to the earliest event, span begin/end pairs fuse
into complete ("X") events, everything else becomes thread-scoped
instants.  Output is STABLE (sorted events, sorted keys) so a golden
test can pin it byte-for-byte, same contract as
``render_prometheus()``.  Load the file at ``ui.perfetto.dev`` or
``chrome://tracing``.
"""
from __future__ import annotations

import json
import os
import threading
import time

import jax

from . import events as _events
from .metrics import LATENCY_BUCKETS_MS, enabled
from .metrics import registry as _registry

__all__ = [
    "span", "traced", "inject", "attach", "context_fields",
    "current_trace_id", "RemoteTraceContext", "render_trace",
    "export_trace", "trace_rank", "trace_host",
]


def trace_rank() -> int:
    """This process's rank for trace/flight attribution: the launcher's
    ``PADDLE_TRAINER_ID`` (0 when unset — single-process labs).  One
    home with the flight recorder's identity fields (``events._rank``)
    so traces and dumps always attribute consistently."""
    return _events._rank()


def trace_host() -> str:
    return _events._host()


# ---------------------------------------------------------------------
# trace context: thread-local (trace_id, open-span stack)
# ---------------------------------------------------------------------
class _Ctx(threading.local):
    def __init__(self):
        self.trace_id = None
        self.stack: list = []   # open span ids, innermost last


_ctx = _Ctx()
_id_lock = threading.Lock()
_next_id = 0


def _new_id() -> int:
    global _next_id
    with _id_lock:
        _next_id += 1
        return _next_id


def _reset():
    """Test hook: fresh ids + context (deterministic goldens)."""
    global _next_id
    with _id_lock:
        _next_id = 0
    _ctx.trace_id = None
    _ctx.stack = []


def current_trace_id():
    return _ctx.trace_id


def inject():
    """The active context as a plain dict to carry across a boundary
    (rpc payload, store value), or None when no span is open (or
    metrics are off)."""
    if not enabled() or not _ctx.stack:
        return None
    return {"trace_id": _ctx.trace_id, "span_id": _ctx.stack[-1]}


def context_fields() -> dict:
    """Trace fields to stamp onto an adjacent structured event (the
    engine's ``serving.dispatch``): ``{}`` outside any span."""
    if not _ctx.stack:
        return {}
    return {"trace_id": _ctx.trace_id, "parent_id": _ctx.stack[-1]}


class attach:
    """Re-establish a remote caller's context for a scope: spans opened
    inside become children of ``ctx["span_id"]`` under the caller's
    ``trace_id``.  A None/invalid ctx attaches nothing (no-op)."""

    def __init__(self, ctx):
        self._ctx = ctx if (isinstance(ctx, dict)
                            and "trace_id" in ctx
                            and "span_id" in ctx) else None
        self._saved = None

    def __enter__(self):
        if self._ctx is not None and enabled():
            self._saved = (_ctx.trace_id, _ctx.stack)
            _ctx.trace_id = self._ctx["trace_id"]
            _ctx.stack = [self._ctx["span_id"]]
        return self

    def __exit__(self, *exc):
        if self._saved is not None:
            _ctx.trace_id, _ctx.stack = self._saved
            self._saved = None
        return False


# fed by ``profiler.Profiler`` while a record window is open (the one
# place its host buffer is filled): fn(name, t0_ns, dur_us, category)
_host_sink = None


class span:
    """``with span("compile", fn="step"): ...`` — THE span of the
    program.  On entry it opens a ``jax.profiler.TraceAnnotation`` (a
    TraceMe: it records only while a profiler session is live, so the
    span lies on the device trace's clock in the ``.xplane.pb``), and
    under ``PDTPU_METRICS`` writes one begin/end pair to the event
    ring, exception-safe (the end event records the error type and
    still pops the stack).  With no session and metrics off it costs
    the TraceMe's enter+exit and nothing else.  ``t0`` is the
    ``perf_counter_ns`` it took as it opened (0 where it took none):
    the call log's marks (``jit._Executable.__call__``).

    The FIRST span on a thread starts a new trace (fresh ``trace_id``);
    nested spans inherit it and point ``parent_id`` at the enclosing
    span.  Attrs must be plain scalars/short strings (ring contract)
    and must not shadow the event schema fields (``kind``/``seq``/
    ``ts``/``name``/``span_id``/``trace_id``/``parent_id``/``tname``).
    """

    __slots__ = ("name", "attrs", "span_id", "t0", "_on", "_root",
                 "_ann", "_sink", "_late")

    # ring record: a ``span.begin``/``span.end`` pair in the trace
    # context.  ``profiler.RecordEvent`` (``_UserSpan``) writes Paddle's
    # single ``span`` record at close instead.
    _paired = True
    _cat = "span"

    def __init__(self, name, **attrs):
        self.name = name
        self.attrs = attrs
        self.t0 = 0             # perf_counter_ns as it opened, if on
        self._on = False
        self._sink = None
        self._late = None

    def __enter__(self):
        name = str(self.name)
        self._ann = jax.profiler.TraceAnnotation(name, **self.attrs)
        self._ann.__enter__()
        self._sink = _host_sink
        self._on = enabled()
        if self._on or self._sink is not None:
            self.t0 = time.perf_counter_ns()
        if not (self._on and self._paired):
            return self
        self._root = not _ctx.stack
        if self._root:
            _ctx.trace_id = _new_id()
        parent = _ctx.stack[-1] if _ctx.stack else None
        self.span_id = _new_id()
        ev = {"name": name, "span_id": self.span_id,
              "trace_id": _ctx.trace_id,
              "tname": threading.current_thread().name}
        if parent is not None:
            ev["parent_id"] = parent
        ev.update(self.attrs)
        _events.emit("span.begin", **ev)
        _ctx.stack.append(self.span_id)
        return self

    def note(self, **attrs):
        """Attributes learned inside the span: they go on its
        ``span.end`` record (the TraceAnnotation took its own on
        entry)."""
        self._late = attrs

    def __exit__(self, etype, exc, tb):
        self._ann.__exit__(etype, exc, tb)
        on, sink = self._on, self._sink
        if not on and sink is None:
            return False
        self._on, self._sink = False, None
        name = str(self.name)
        dur_ns = time.perf_counter_ns() - self.t0
        if sink is not None:
            sink(name, self.t0, dur_ns // 1000, self._cat)
        if not on:
            return False
        if not self._paired:
            _events.emit("span", name=self.name,
                         dur_us=int(dur_ns // 1000))
            return False
        # pop OUR id even if an attach/reset raced the scope
        if _ctx.stack and _ctx.stack[-1] == self.span_id:
            _ctx.stack.pop()
        elif self.span_id in _ctx.stack:
            _ctx.stack.remove(self.span_id)
        fields = {"name": name, "span_id": self.span_id,
                  "trace_id": _ctx.trace_id,
                  "dur_us": round(dur_ns / 1e3, 1)}
        if etype is not None:
            fields["error"] = etype.__name__
        if self._late:
            fields.update(self._late)
        _events.emit("span.end", **fields)
        if self._root and not _ctx.stack:
            _ctx.trace_id = None
        return False


class _UserSpan(span):
    """``profiler.RecordEvent``'s span: the same TraceAnnotation and
    host-buffer feed, Paddle's one ``span`` ring record at close, and
    no part in the trace context."""

    __slots__ = ()
    _paired = False
    _cat = "user"


def traced(name=None, **attrs):
    """``@traced`` / ``@traced("phase", k=v)``: wrap a function in a
    :func:`span` named after it (or ``name``)."""
    import functools

    def deco(fn):
        sname = name or getattr(fn, "__name__", "span")

        @functools.wraps(fn)
        def wrapper(*a, **k):
            with span(sname, **attrs):
                return fn(*a, **k)
        return wrapper

    if callable(name):               # bare @traced
        fn, name = name, None
        return deco(fn)
    return deco


class RemoteTraceContext:
    """Picklable wrapper carrying a trace context alongside an rpc
    callable: the SERVER side attaches the caller's context and runs
    the call under an ``rpc.server`` span, so the remote work lands in
    the caller's trace.  Rides the existing ``(fn, args, kwargs)`` wire
    frame — the rpc protocol itself is unchanged, and with metrics off
    the client never wraps (bitwise pre-observability payloads)."""

    def __init__(self, ctx, fn):
        self.ctx = ctx
        self.fn = fn

    def __call__(self, *args, **kwargs):
        with attach(self.ctx), \
                span("rpc.server",
                     fn=getattr(self.fn, "__name__", str(self.fn)),
                     rank=trace_rank()):
            return self.fn(*args, **kwargs)


# ---------------------------------------------------------------------
# Chrome/Perfetto trace-event export
# ---------------------------------------------------------------------
# ring kinds -> export policy.  Spans fuse into "X" complete events;
# profiler span/op kinds already carry dur_us (recorded at close);
# everything else becomes a thread-scoped instant on a stable track.
_RUNTIME_KINDS = ("retry.", "guard.", "fault.", "preempt.", "flight.",
                  "compile.")


def _track_of(ev) -> str:
    kind = ev.get("kind", "")
    if kind.startswith("span.") or kind in ("span", "op"):
        return str(ev.get("tname", "main"))
    if kind.startswith("serving."):
        slot = ev.get("slot")
        return f"engine/slot{int(slot)}" if slot is not None \
            else "engine"
    for pfx in _RUNTIME_KINDS:
        if kind.startswith(pfx):
            return "runtime"
    return "events"


_META_FIELDS = ("seq", "ts", "kind", "tname")


def _args_of(ev) -> dict:
    return {k: v for k, v in ev.items() if k not in _META_FIELDS}


def render_trace(events=None, rank=None, host=None) -> dict:
    """The ring (or ``events``) as a Chrome trace-event dict.

    One Perfetto *process* per rank, one *thread* (track) per
    thread / engine slot / runtime stream; ``span.begin``/``span.end``
    pairs fuse into complete events, unmatched halves degrade to
    ``B``/``E`` phase events so a crash mid-span still renders.
    Deterministic: events sorted by (timestamp, seq), keys sorted at
    serialization — goldens pin the exact output."""
    evs = [e for e in (_events.tail() if events is None else events)
           if e is not None]
    rank = trace_rank() if rank is None else int(rank)
    host = trace_host() if host is None else str(host)
    if evs:
        base = min(float(e.get("ts", 0.0)) for e in evs)
    else:
        base = 0.0

    def us(ts):
        return round((float(ts) - base) * 1e6, 1)

    tracks: dict[str, int] = {}

    def tid(track):
        if track not in tracks:
            tracks[track] = len(tracks) + 1
        return tracks[track]

    out = []
    open_spans: dict = {}   # span_id -> (begin event, tid)
    for ev in sorted(evs, key=lambda e: (float(e.get("ts", 0.0)),
                                         e.get("seq", 0))):
        kind = ev.get("kind", "")
        if kind == "span.begin":
            open_spans[ev.get("span_id")] = (ev, tid(_track_of(ev)))
        elif kind == "span.end":
            # the END event carries no tname: the matched begin's
            # track places it; only orphans fall back to "main"
            beg = open_spans.pop(ev.get("span_id"), None)
            args = _args_of(ev)
            if beg is not None:
                bev, bt = beg
                args = dict(_args_of(bev), **args)
                dur = args.pop("dur_us", 0.0)
                args.pop("name", None)   # lifted into the event name
                out.append({"name": str(ev.get("name", "span")),
                            "cat": "span", "ph": "X",
                            "ts": us(bev.get("ts", 0.0)),
                            "dur": round(float(dur), 1),
                            "pid": rank, "tid": bt, "args": args})
            else:   # end without a begin in the ring (wrapped away)
                args.pop("name", None)
                out.append({"name": str(ev.get("name", "span")),
                            "cat": "span", "ph": "E",
                            "ts": us(ev.get("ts", 0.0)),
                            "pid": rank, "tid": tid(_track_of(ev)),
                            "args": args})
        elif kind in ("span", "op"):
            t = tid(_track_of(ev))
            # profiler events: one record at close carrying dur_us
            dur = float(ev.get("dur_us", 0.0))
            pargs = _args_of(ev)
            pargs.pop("name", None)
            pargs.pop("dur_us", None)
            out.append({"name": str(ev.get("name", kind)),
                        "cat": "profiler", "ph": "X",
                        "ts": round(us(ev.get("ts", 0.0)) - dur, 1),
                        "dur": round(dur, 1),
                        "pid": rank, "tid": t, "args": pargs})
        else:
            out.append({"name": kind, "cat": kind.split(".")[0],
                        "ph": "i", "s": "t",
                        "ts": us(ev.get("ts", 0.0)),
                        "pid": rank, "tid": tid(_track_of(ev)),
                        "args": _args_of(ev)})
    # crash-truncated spans: render the begin so the open phase shows
    for sid in sorted(open_spans, key=lambda s: (s is None, s)):
        bev, bt = open_spans[sid]
        bargs = _args_of(bev)
        bargs.pop("name", None)
        out.append({"name": str(bev.get("name", "span")),
                    "cat": "span", "ph": "B",
                    "ts": us(bev.get("ts", 0.0)),
                    "pid": rank, "tid": bt, "args": bargs})
    # complete ("X") events carry their BEGIN timestamp but were
    # appended at end-event order: one final stable sort
    out.sort(key=lambda e: (e["ts"], e["tid"], e["ph"], e["name"]))
    meta = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
             "args": {"name": f"rank{rank} ({host})"}}]
    for track in sorted(tracks, key=lambda k: tracks[k]):
        meta.append({"name": "thread_name", "ph": "M", "pid": rank,
                     "tid": tracks[track], "args": {"name": track}})
    return {"displayTimeUnit": "ms", "traceEvents": meta + out}


def export_trace(path, events=None, rank=None, host=None):
    """Write the ring (or ``events``) as a Chrome/Perfetto trace JSON
    file and return the path — or None with metrics off (no stray
    files, same contract as ``events.dump``).  Observes the export
    wall into ``trace.export_ms`` (default registry)."""
    if not enabled():
        return None
    t0 = time.perf_counter()
    rec = render_trace(events, rank=rank, host=host)
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
    _registry().histogram(
        "trace.export_ms", "export_trace render+write wall",
        LATENCY_BUCKETS_MS).observe(
            (time.perf_counter() - t0) * 1e3)
    return path
