"""Serving-side observability: per-request timelines over engine events.

The serving engine's mixed program makes naive latency measurement
impossible from the outside: prefill chunks and decode tokens of many
requests share ONE dispatch, so per-request phase latencies must be
reconstructed from the engine's own scheduling events — which is what
:class:`ServingTimelines` does.  The engine calls the lifecycle hooks
(enqueued -> admitted -> prefill_chunk(s) -> token(s) ->
preempted/requeued -> retired) as it schedules; the timelines object

* emits the corresponding structured events into the process ring
  (``serving.enqueued`` / ``serving.admitted`` / ``serving.first_token``
  / ``serving.decode_window`` / ``serving.verify_window`` /
  ``serving.preempted`` / ``serving.retired`` — the flight recorder's
  request-level story), and
* derives the latency metrics the TPU serving literature frames
  comparisons in: queue-time, TTFT (enqueue -> first generated token),
  TPOT (steady-state inter-token), decode-tokens-per-window, plus
  preemption-count and cache-hit-token histograms labeled by
  ``finish_reason``.

Every hook early-returns when ``PDTPU_METRICS=off``; with it on, a hook
is a dict lookup, a clock read and a histogram observe.

:class:`RegistryCounters` is the adapter that re-backs the engine's
``stats`` dict onto registry counters: same keys, same int values, same
iteration order, so the PR5-PR7 gauge/counter assertions hold unchanged
while ``engine.metrics()`` exposes the same numbers as a snapshot.
"""
from __future__ import annotations

import time

from . import events as _events
from .metrics import (COUNT_BUCKETS, LATENCY_BUCKETS_MS, Registry,
                      enabled)

__all__ = ["ServingTimelines", "RegistryCounters"]


class RegistryCounters:
    """Dict-like counter block backed by a :class:`Registry`.

    ``always=True`` counters: these values ARE the engine's public
    ``stats`` contract, which predates the observability runtime — the
    metrics flag must not zero them.
    """

    def __init__(self, registry: Registry, names, prefix="serving"):
        self._names = tuple(names)
        self._c = {n: registry.counter(f"{prefix}.{n}", always=True)
                   for n in self._names}

    def __getitem__(self, k):
        return self._c[k].value

    def __setitem__(self, k, v):
        self._c[k].set(v)

    def __contains__(self, k):
        return k in self._c

    def as_dict(self) -> dict:
        """Plain dict in declaration order — byte-compatible with the
        pre-observability ``dict(self._stats)``."""
        return {n: self._c[n].value for n in self._names}


class _ReqTL:
    __slots__ = ("enq", "admit", "first_tok", "last_tok", "n_toks",
                 "cache_hit_tokens")

    def __init__(self, enq):
        self.enq = enq
        self.admit = None
        self.first_tok = None
        self.last_tok = None
        self.n_toks = 0
        self.cache_hit_tokens = 0


class ServingTimelines:
    def __init__(self, registry: Registry, clock=None):
        self._clock = time.monotonic if clock is None else clock
        self._open: dict = {}
        self._reg = registry
        self._h_queue = registry.histogram(
            "serving.queue_ms", "enqueue -> first admission wait",
            LATENCY_BUCKETS_MS)
        self._h_ttft = registry.histogram(
            "serving.ttft_ms", "enqueue -> first generated token",
            LATENCY_BUCKETS_MS)
        self._h_tpot = registry.histogram(
            "serving.tpot_ms", "steady-state inter-token latency",
            LATENCY_BUCKETS_MS)
        self._h_window = registry.histogram(
            "serving.decode_tokens_per_window",
            "tokens accepted per decode-window dispatch", COUNT_BUCKETS)
        self._h_dispatch = registry.histogram(
            "serving.dispatch_ms", "per-dispatch round trip",
            LATENCY_BUCKETS_MS)
        self._h_spec = registry.histogram(
            "serving.spec_accepted_per_step",
            "tokens emitted per speculative verify step per slot "
            "(accepted drafts + the free target token)", COUNT_BUCKETS)

    # labeled (by finish_reason) metrics are created on first use — the
    # registry get-or-creates, so repeat reasons share one object
    def _finished(self, reason):
        return self._reg.counter(
            "serving.finished", "retired requests by finish_reason",
            labels={"reason": reason})

    def _h_preempt(self, reason):
        return self._reg.histogram(
            "serving.preemptions_per_request",
            "preempt-and-requeue count over a request's lifetime",
            COUNT_BUCKETS, labels={"reason": reason})

    def _h_cache_hit(self, reason):
        return self._reg.histogram(
            "serving.cache_hit_tokens_per_request",
            "prefix-cache tokens restored instead of re-prefilled",
            COUNT_BUCKETS, labels={"reason": reason})

    # --------------------------------------------------- lifecycle ----
    def enqueued(self, rid, prompt_len, max_new_tokens):
        if not enabled():
            return
        self._open[rid] = _ReqTL(self._clock())
        _events.emit("serving.enqueued", rid=rid,
                     prompt_len=int(prompt_len),
                     max_new_tokens=int(max_new_tokens))

    def admitted(self, rid, slot, cached_tokens=0, resume_len=0):
        if not enabled():
            return
        now = self._clock()
        tl = self._open.get(rid)
        if tl is not None:
            tl.cache_hit_tokens += int(cached_tokens)
            if tl.admit is None:            # first admission only: a
                tl.admit = now              # requeue is not queue time
                self._h_queue.observe((now - tl.enq) * 1e3)
        _events.emit("serving.admitted", rid=rid, slot=int(slot),
                     cached_tokens=int(cached_tokens),
                     resume_len=int(resume_len))

    def prefill_chunk(self, rid, slot, take, off):
        if not enabled():
            return
        _events.emit("serving.prefill_chunk", rid=rid, slot=int(slot),
                     tokens=int(take), offset=int(off))

    def token(self, rid):
        """One generated token accepted for ``rid`` (any dispatch
        shape). The first one closes the TTFT window."""
        if not enabled():
            return
        now = self._clock()
        tl = self._open.get(rid)
        if tl is None:
            return
        tl.n_toks += 1
        tl.last_tok = now
        if tl.first_tok is None:
            tl.first_tok = now
            self._h_ttft.observe((now - tl.enq) * 1e3)
            _events.emit("serving.first_token", rid=rid,
                         ttft_ms=round((now - tl.enq) * 1e3, 3))

    def decode_window(self, tokens, live_slots):
        if not enabled():
            return
        self._h_window.observe(int(tokens))
        _events.emit("serving.decode_window", tokens=int(tokens),
                     live_slots=int(live_slots))

    def verify_window(self, rid, proposed, accepted, emitted):
        """One slot's speculative verify outcome (ISSUE 9):
        ``proposed`` drafts submitted, ``accepted`` of them agreed
        with the target, ``emitted`` tokens advanced (accepted + the
        free target token, clipped by eos/stop)."""
        if not enabled():
            return
        self._h_spec.observe(int(emitted))
        _events.emit("serving.verify_window", rid=rid,
                     proposed=int(proposed), accepted=int(accepted),
                     emitted=int(emitted))

    def dispatch(self, kind, ms):
        if not enabled():
            return
        self._h_dispatch.observe(float(ms))
        # stamp the active trace context (ISSUE 12): the engine opens a
        # serving.dispatch span around each dispatch, so the timeline
        # event carries trace_id/parent_id — the hop-level evidence a
        # cross-worker trace (rpc-propagated) ends in
        from . import tracing as _tracing
        _events.emit("serving.dispatch", name=str(kind),
                     ms=round(float(ms), 3),
                     **_tracing.context_fields())

    def preempted(self, rid, tokens_done):
        if not enabled():
            return
        _events.emit("serving.preempted", rid=rid,
                     tokens_done=int(tokens_done))

    def migrated(self, rid, direction, pages=0, phase=""):
        """A live migration moved ``rid`` across engines (ISSUE 20).
        ``direction`` is ``"out"`` — this engine silently relinquished
        the request (no finish reason: its open timeline closes here
        and the DESTINATION's timeline carries the request to
        retirement) — or ``"in"`` (restored here)."""
        if direction == "out":
            self._open.pop(rid, None)
        if not enabled():
            return
        _events.emit("serving.migrated", rid=rid,
                     direction=str(direction), pages=int(pages),
                     phase=str(phase))

    def retired(self, rid, reason, n_tokens, preemptions=0):
        if not enabled():
            self._open.pop(rid, None)
            return
        tl = self._open.pop(rid, None)
        self._finished(reason).inc()
        self._h_preempt(reason).observe(int(preemptions))
        if tl is not None:
            self._h_cache_hit(reason).observe(tl.cache_hit_tokens)
            if tl.admit is None:
                # retired WITHOUT ever being admitted (deadline expired
                # in the queue, queued cancel): its whole life was
                # queue time. Overload understates queueing without
                # this — the longest waits are exactly the expired ones
                self._h_queue.observe((self._clock() - tl.enq) * 1e3)
            if tl.first_tok is not None and tl.n_toks >= 2:
                self._h_tpot.observe(
                    (tl.last_tok - tl.first_tok) * 1e3
                    / (tl.n_toks - 1))
        _events.emit("serving.retired", rid=rid, finish_reason=reason,
                     tokens=int(n_tokens), preemptions=int(preemptions))
