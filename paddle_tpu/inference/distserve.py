"""Disaggregated prefill/decode serving over TP-sharded engine workers
(ISSUE 13; ROADMAP item #2 — the last single-chip wall).

Production serving splits COMPUTE-bound prefill from LATENCY-bound
decode (TPLA, PAPERS.md #4): a prefill burst that lands on a colocated
engine steals whole mixed-program dispatches from every resident
decode, so decode p99 tracks arrival bursts instead of the hardware.
Here the two phases run as SEPARATE worker groups of
:class:`~paddle_tpu.inference.ContinuousBatchingEngine` instances —
each group optionally TP-sharded over its own mesh (``mesh=`` /
``tp_axis=`` engine kwargs; ``models/generation.py`` TP section) —
with a KV-PAGE HANDOFF between them:

* admission prefills on the prefill group (chunked, ragged-batched —
  the engine's normal mixed program, ``max_new_tokens=1`` so the slot
  stops right after its first token);
* the moment a request's first token exists, its live pages (+ int8
  scale side-pools), block-table row and scheduler state serialize
  into a :class:`KVPageTransport` payload — ONLY the request's written
  pages move, nothing pool-shaped — and ship under bounded
  ``resilience.retry`` (``engine_handoff_transient`` drills the
  transient path);
* the decode group imports the payload: page ids remap into its own
  free list, bytes scatter in one compiled dispatch, and the request
  continues through the UNTOUCHED decode-window / speculative paths.
  Prefix-cache publish happens on the decode side (retire publishes
  the decode engine's pages, and ``import_request`` retains pages the
  decode cache already indexes for the same prefix), so cached
  prefixes survive handoff; the prefill side keeps its own cache for
  cross-request prompt reuse before the handoff.

Because the ragged kernel treats block tables and lengths as pure
data, the handoff is a byte copy plus a table rewrite — no recompiles,
and the decode stream is BITWISE the colocated engine's (greedy
decode is deterministic and KV bytes are a pure function of the token
prefix; ``tests/test_distserve.py`` pins colocated-vs-disagg token
equality with pool conservation on both groups).

Failure model: a handoff transient retries bounded
(``serving_disagg_handoff_retries``); a lost decode worker
(``engine_decode_worker_lost`` drill) discards the payload and
REQUEUES the request to the prefill group for a from-scratch
re-prefill — bitwise, only the ``requeues`` counter moves.

Observability: every handoff runs under a ``serving.handoff`` tracing
span, emits a ``serving.handoff`` event (rid/bytes/ms) into the ring,
and feeds the coordinator registry's ``serving.handoff_ms`` histogram
and ``serving.handoff_bytes``/``serving.handoffs``/``serving.requeues``
counters.
"""
from __future__ import annotations

import pickle
import time
from collections import deque

import numpy as np

from ..core.state import get_flag as _get_flag
from ..observability import Registry as _ObsRegistry
from ..observability import events as _events
from ..observability import tracing as _tracing
from ..observability import watchdog as _watchdog
from ..observability.metrics import LATENCY_BUCKETS_MS
from ..resilience import faults
from ..resilience.retry import retry_call
from ..resilience.serving import (SITE_DECODE_WORKER_LOST,
                                  SITE_HANDOFF_TRANSIENT,
                                  SITE_MIGRATION_TRANSIENT)
from .engine import CompletedRequest, ContinuousBatchingEngine

__all__ = ["DisaggServer", "KVPageTransport", "register_decode_worker",
           "rpc_deliver_payload", "rpc_restore_payload"]


# ------------------------------------------------------------------ rpc
# decode workers reachable over distributed/rpc register their engine
# here (process-global, like the rpc agent itself); the transport ships
# pickled payload bytes to ``rpc_deliver_payload`` on the worker
_DECODE_WORKERS: dict = {}


def register_decode_worker(name: str, engine) -> None:
    """Expose ``engine`` to rpc handoffs under ``name`` (call on the
    decode worker process after ``rpc.init_rpc``)."""
    _DECODE_WORKERS[str(name)] = engine


def rpc_deliver_payload(name: str, data: bytes, max_new_tokens: int,
                        deadline_ms=None):
    """Server-side half of an rpc handoff: deserialize and import into
    the registered decode engine.  Returns the imported rid, or None
    when the worker has no capacity right now (the caller retries)."""
    eng = _DECODE_WORKERS.get(str(name))
    if eng is None:
        raise KeyError(f"no decode worker registered as {name!r}")
    return eng.import_request(pickle.loads(data), max_new_tokens,
                              deadline_ms=deadline_ms)


def rpc_restore_payload(name: str, data: bytes):
    """Server-side half of an rpc live migration (ISSUE 20):
    deserialize a ``snapshot_request`` payload and restore it into the
    registered engine.  Returns the restored rid, or None when the
    engine has no capacity right now (the caller retries)."""
    eng = _DECODE_WORKERS.get(str(name))
    if eng is None:
        raise KeyError(f"no worker registered as {name!r}")
    return eng.restore_request(pickle.loads(data))


class KVPageTransport:
    """Serialize + ship one request's live KV pages between engines.

    The payload (``engine.export_request``) pickles to bytes even for
    the in-process path, so every handoff exercises the real wire
    encoding; ``to=`` names an rpc worker (``distributed/rpc``) that
    registered its engine via :func:`register_decode_worker`, in which
    case the bytes cross the socket.  ``ship`` runs under bounded
    ``resilience.retry`` on transient ``ConnectionError`` — the
    ``engine_handoff_transient`` fault site drills exactly that.
    """

    def __init__(self, to=None, retries=None):
        self.to = to
        self.retries = int(_get_flag("serving_disagg_handoff_retries")
                           if retries is None else retries)

    def ship(self, payload, dst_engine, max_new_tokens,
             deadline_ms=None, on_retry=None):
        """Move ``payload`` into ``dst_engine`` (or the rpc worker when
        ``to`` is set).  Returns ``(rid_or_None, n_bytes)`` — None when
        the destination has no capacity yet (retry after a step)."""
        rid = payload["rid"]
        data = pickle.dumps(payload)

        def _send():
            faults.maybe_raise(SITE_HANDOFF_TRANSIENT, str(rid))
            if self.to is not None:
                from ..distributed.rpc import rpc_sync
                return rpc_sync(self.to, rpc_deliver_payload,
                                args=(self.to, data, max_new_tokens,
                                      deadline_ms))
            return dst_engine.import_request(
                pickle.loads(data), max_new_tokens,
                deadline_ms=deadline_ms)

        out = retry_call(_send, max_attempts=max(1, self.retries + 1),
                         base_delay=0.005, max_delay=0.05,
                         retry_on=(ConnectionError,),
                         on_retry=on_retry)
        return out, len(data)

    def ship_snapshot(self, payload, dst_engine, on_retry=None):
        """Live-migration half (ISSUE 20): move a full-request
        ``snapshot_request`` payload into ``dst_engine.restore_request``
        (or the rpc worker when ``to`` is set) under the same bounded
        retry discipline — the ``router_migration_transient`` fault
        site sits INSIDE the retried closure, so a ``*N`` drill is
        absorbed by N retries exactly like a real transient.  A torn
        payload surfaces ``MigrationError`` (PDT-E025) from the
        restore CRC check UNRETRIED (it is not a ConnectionError): the
        source keeps the request.  Returns ``(rid_or_None, n_bytes)``
        — None when the destination has no capacity yet."""
        rid = payload["rid"]
        data = pickle.dumps(payload)

        def _send():
            faults.maybe_raise(SITE_MIGRATION_TRANSIENT, str(rid))
            if self.to is not None:
                from ..distributed.rpc import rpc_sync
                return rpc_sync(self.to, rpc_restore_payload,
                                args=(self.to, data))
            return dst_engine.restore_request(pickle.loads(data))

        out = retry_call(_send, max_attempts=max(1, self.retries + 1),
                         base_delay=0.005, max_delay=0.05,
                         retry_on=(ConnectionError,),
                         on_retry=on_retry)
        return out, len(data)


class _DisaggReq:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos", "deadline",
                 "state", "requeues")

    def __init__(self, rid, prompt, max_new_tokens, eos, deadline):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos = eos
        self.deadline = deadline  # ABSOLUTE clock seconds | None: armed
        # once at coordinator admission, so the prefill engine, the
        # handoff wait and the decode engine all spend from ONE budget
        # (a per-engine re-arm would let a request run ~2x its TTL)
        self.state = "pending"   # pending|prefill|ready|decode|done
        self.requeues = 0


class DisaggServer:
    """Coordinator for disaggregated prefill/decode serving.

    ``prefill_workers``/``decode_workers`` engine instances are built
    from the shared ``model`` plus per-group kwargs
    (``prefill_kwargs``/``decode_kwargs`` — pool geometry, mesh=/
    tp_axis= TP sharding, kv_quant ...; both groups must agree on
    ``page_size`` and ``kv_quant``, the KV wire layout).  The API
    mirrors the engine: :meth:`add_request`, :meth:`step` (returns
    completed requests), :meth:`run` to drain, ``stats`` /
    :meth:`metrics`.

    A request's life: pending -> prefill group (``max_new_tokens=1``)
    -> first token -> export + :class:`KVPageTransport` handoff ->
    decode group import -> decode windows -> completion surfaces from
    :meth:`step`.  An eos at the first token completes on the prefill
    side without a handoff; prefill-side failures/timeouts surface as
    final results.  ``engine_decode_worker_lost`` requeues to the
    prefill group (bitwise re-prefill).

    Both groups inherit the engine's compile-time program audit: every
    cached program (import scatter, decode windows, TP wrappers) runs
    through the whole-program jaxpr analyzer once per geometry at
    first compile (``analysis/program.py``; ``PDTPU_ANALYSIS``-gated).
    """

    def __init__(self, model, *, prefill_workers=None,
                 decode_workers=None, transport=None,
                 prefill_kwargs=None, decode_kwargs=None, clock=None):
        npf = int(_get_flag("serving_disagg_prefill_workers")
                  if prefill_workers is None else prefill_workers)
        ndc = int(_get_flag("serving_disagg_decode_workers")
                  if decode_workers is None else decode_workers)
        if npf < 1 or ndc < 1:
            raise ValueError("DisaggServer needs >= 1 prefill and >= 1 "
                             "decode worker")
        pk = dict(prefill_kwargs or {})
        dk = dict(decode_kwargs or {})
        if clock is not None:
            pk.setdefault("clock", clock)
            dk.setdefault("clock", clock)
        self.prefill_group = [ContinuousBatchingEngine(model, **pk)
                              for _ in range(npf)]
        self.decode_group = [ContinuousBatchingEngine(model, **dk)
                             for _ in range(ndc)]
        p0, d0 = self.prefill_group[0], self.decode_group[0]
        if (p0.page_size != d0.page_size
                or p0.kv_quant != d0.kv_quant):
            raise ValueError(
                "prefill and decode groups must share page_size and "
                "kv_quant — they are the KV handoff wire layout")
        self.transport = transport or KVPageTransport()
        self._clock = time.monotonic if clock is None else clock
        self._reqs: dict = {}            # rid -> _DisaggReq
        self._pending: deque = deque()   # rids awaiting prefill entry
        self._ready: deque = deque()     # (rid, payload) awaiting import
        self._finalized: list = []       # coordinator-side completions
        # (timeouts of parked requests) surfaced by the NEXT step() —
        # exception-safe: a handoff error later in the same tick
        # cannot lose them
        self._next_rid = 0
        self._rr = 0                     # decode-group round robin
        self._step_n = 0
        self._done_at: dict = {}         # rid -> step_n when finalized
        self._registry = _ObsRegistry("serving_disagg")
        reg = self._registry
        self._c_handoffs = reg.counter("serving.handoffs", always=True)
        self._c_bytes = reg.counter("serving.handoff_bytes",
                                    always=True)
        self._c_requeues = reg.counter("serving.requeues", always=True)
        self._c_retries = reg.counter("serving.handoff_retries",
                                      always=True)
        self._h_handoff = reg.histogram(
            "serving.handoff_ms", "export -> decode-import wall time",
            LATENCY_BUCKETS_MS)

    # ------------------------------------------------------------ API --
    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    request_id=None, deadline_ms=None, requeue=False):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        # eager validation against the DECODE group's budget — the
        # group that must hold the full sequence.  The prefill group
        # only ever sees prompt+1 tokens, so without this check an
        # oversized request would admit cleanly and then crash
        # import_request mid-handoff (the engine's own add_request
        # rejects these at admission for exactly this reason).
        dec = self.decode_group[0]
        total = prompt.size + int(max_new_tokens)
        if total > dec.max_seq_len:
            raise ValueError(
                f"request needs {total} tokens > decode-group "
                f"max_seq_len {dec.max_seq_len}")
        need_full = -(-total // dec.page_size)
        if need_full > dec.total_pages - 1:
            from ..core.errors import PageBudgetError
            raise PageBudgetError(
                f"request needs {need_full} pages but the decode "
                f"pool only has {dec.total_pages - 1} "
                f"[{PageBudgetError.error_code}]")
        pre = self.prefill_group[0]
        if prompt.size + 1 > pre.max_seq_len:
            raise ValueError(
                f"prompt needs {prompt.size + 1} tokens > "
                f"prefill-group max_seq_len {pre.max_seq_len}")
        need_pf = -(-(prompt.size + 1) // pre.page_size)
        if need_pf > pre.total_pages - 1:
            from ..core.errors import PageBudgetError
            raise PageBudgetError(
                f"prompt needs {need_pf} pages but the prefill pool "
                f"only has {pre.total_pages - 1} "
                f"[{PageBudgetError.error_code}]")
        if request_id is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            rid = request_id
            if isinstance(rid, int):
                self._next_rid = max(self._next_rid, rid + 1)
            if rid in self._reqs and self._reqs[rid].state != "done":
                raise ValueError(f"request_id {rid!r} already in flight")
        deadline = (self._clock() + float(deadline_ms) / 1e3) \
            if deadline_ms else None
        r = _DisaggReq(rid, prompt, max_new_tokens,
                       eos_token_id, deadline)
        if requeue:
            # a fleet-router requeue: the request's prefill demand was
            # already counted on its first admission — mark it so
            # _submit_pending's engine admission skips the re-count
            r.requeues = 1
        self._reqs[rid] = r
        self._pending.append(rid)
        return rid

    def _remaining_ms(self, r):
        """Milliseconds left on ``r``'s coordinator-armed deadline
        (None = no deadline).  Engines get the REMAINING budget, never
        a fresh one."""
        if r.deadline is None:
            return None
        return (r.deadline - self._clock()) * 1e3

    @property
    def has_work(self):
        return bool(self._pending) or bool(self._ready) \
            or bool(self._finalized) or any(
                e.has_work for e in self.prefill_group
                + self.decode_group)

    def cached_prefix_tokens(self, ids) -> int:
        """Fleet-router affinity query: the longest page-aligned
        prefix of ``ids`` any PREFILL engine already holds (re-prefill
        lands on the prefill group, so that is where a routed prompt's
        cached pages pay off)."""
        return max(e.cached_prefix_tokens(ids)
                   for e in self.prefill_group)

    def pending_requests(self):
        """Request ids still in flight across the coordinator and both
        groups — the fleet router's live-load gauge."""
        return [rid for rid, r in self._reqs.items()
                if r.state != "done"]

    @property
    def stats(self):
        """Coordinator counters plus per-group aggregates."""
        d = {
            "handoffs": self._c_handoffs.value,
            "handoff_bytes": self._c_bytes.value,
            "handoff_retries": self._c_retries.value,
            "requeues": self._c_requeues.value,
            "pending": len(self._pending),
            "ready": len(self._ready),
        }
        for name, group in (("prefill", self.prefill_group),
                            ("decode", self.decode_group)):
            st = [e.stats for e in group]
            d[f"{name}_admitted"] = sum(s["admitted"] for s in st)
            d[f"{name}_tokens_generated"] = sum(
                s["tokens_generated"] for s in st)
            d[f"{name}_pages_in_use"] = sum(
                s["pages_in_use"] for s in st)
        return d

    def metrics(self) -> dict:
        """The coordinator registry snapshot (handoff histograms and
        counters).  Per-request serving timelines live on the group
        engines — ``server.decode_group[0].metrics()`` has the decode
        TTFT/TPOT story."""
        return self._registry.snapshot()

    def slo_status(self) -> dict:
        """Per-group SLO status (ISSUE 14): every worker engine's
        :meth:`ContinuousBatchingEngine.slo_status` list, keyed
        ``prefill``/``decode``.  Specs arm through the per-group
        engine kwargs (``prefill_kwargs``/``decode_kwargs`` ``slo=``)
        or the ``serving_slo`` flag — disaggregation exists to protect
        decode TPOT tails, so the decode group is where the TPOT
        objective normally lives."""
        return {
            "prefill": [s for e in self.prefill_group
                        for s in e.slo_status()],
            "decode": [s for e in self.decode_group
                       for s in e.slo_status()],
        }

    def step(self):
        """One coordinator tick: feed pending admissions to the
        prefill group, step it, export + hand off first-token slots,
        import ready payloads into the decode group, step it.  Returns
        the requests completed this tick (decode completions plus
        prefill-side finals: first-token eos, failures, timeouts, and
        coordinator-side deadline expiries)."""
        self._step_n += 1
        out = list(self._finalized)      # survivors of a prior tick's
        self._finalized.clear()          # mid-loop exception included
        self._submit_pending()
        for eng in self.prefill_group:
            for c in eng.step():
                done = self._on_prefill_complete(c)
                if done is not None:
                    out.append(done)
            self._export_first_tokens(eng)
        self._deliver_ready()
        out.extend(self._finalized)
        self._finalized.clear()
        for eng in self.decode_group:
            for c in eng.step():
                r = self._reqs.get(c.request_id)
                if r is not None:
                    self._mark_done(r)
                out.append(c)
        # prune bookkeeping for requests finalized a few ticks ago:
        # the entry is only needed to swallow the prefill engine's
        # one-tick-late 'length' echo, and a long-running coordinator
        # must not retain every dead request's prompt forever
        for rid, n in list(self._done_at.items()):
            if n <= self._step_n - 3:
                del self._done_at[rid]
                self._reqs.pop(rid, None)
        return out

    def _mark_done(self, r):
        r.state = "done"
        self._done_at[r.rid] = self._step_n

    def _timeout(self, r, tokens=()):
        """Finalize ``r`` at the coordinator (deadline expired while
        pending or parked in the handoff queue — windows no engine
        sweep covers).  Goes through ``_finalized`` so a handoff
        exception later in the same tick cannot lose the result."""
        self._mark_done(r)
        self._finalized.append(CompletedRequest(
            r.rid, r.prompt, np.asarray(list(tokens), np.int32),
            "timeout"))

    def run(self, max_steps=10000):
        """Drain: step until every request completes.  Returns
        {request_id: CompletedRequest} in completion order."""
        import warnings
        done = {}
        for _ in range(max_steps):
            if not self.has_work:
                break
            for c in self.step():
                done[c.request_id] = c
        if self.has_work:
            warnings.warn(
                f"DisaggServer.run: step budget ({max_steps}) "
                f"exhausted with requests still in flight",
                RuntimeWarning, stacklevel=2)
        return done

    # ----------------------------------------------------- internals --
    def _submit_pending(self):
        kept = deque()
        # the in-flight guard must union EVERY prefill engine: after a
        # worker-lost requeue the old slot may still be draining on a
        # different engine than the one the balancer would pick, and a
        # double admission would surface a truncated duplicate result
        in_flight = set()
        for e in self.prefill_group:
            in_flight |= {q.rid for q in e._queue} | {
                s.req.rid for s in e._slots if s.req is not None}
        try:
            while self._pending:
                rid = self._pending.popleft()
                r = self._reqs[rid]
                if r.state == "done":
                    continue   # finalized elsewhere (engine-side
                               # timeout of the old slot): drop
                rem = self._remaining_ms(r)
                if rem is not None and rem <= 0:
                    self._timeout(r)
                    continue
                if rid in in_flight:  # old slot still draining after
                    kept.append(rid)  # a worker-lost requeue: wait
                    continue
                eng = min(self.prefill_group,
                          key=lambda e: len(e._queue))
                try:
                    # prefill side generates exactly the FIRST token;
                    # the real budget rides the payload to decode.
                    # requeues (worker-lost, router requeue) re-admit
                    # a request whose demand is already counted —
                    # requeue=True keeps prefill_tokens_requested a
                    # once-per-request demand figure while computed
                    # meters the actual (net-of-cache) recompute
                    eng.add_request(r.prompt, 1, eos_token_id=r.eos,
                                    request_id=rid, deadline_ms=rem,
                                    requeue=r.requeues > 0)
                except Exception:
                    kept.append(rid)      # keep: retry next tick
                    raise
                r.state = "prefill"
        finally:
            # exception-safe: whatever this tick did not reach stays
            # queued instead of vanishing mid-loop
            kept.extend(self._pending)
            self._pending = kept

    def _export_first_tokens(self, eng):
        """Export every prefill slot that just produced its first
        token (phase flipped to decode); the slot retires on the
        engine's next step and its pages publish into the PREFILL
        side's prefix cache — export is a copy, not a steal."""
        for s in eng._slots:
            if s.req is None or s.phase != "decode":
                continue
            r = self._reqs.get(s.req.rid)
            if r is None or r.state != "prefill":
                continue
            t0 = int(s.out_toks[-1]) if s.out_toks else None
            if t0 is not None and r.eos is not None \
                    and t0 == int(r.eos):
                # eos at the first token: complete on the prefill side
                # (the engine's own retire will emit reason "stop" —
                # _on_prefill_complete surfaces it)
                r.state = "eos_at_first"
                continue
            if r.max_new_tokens <= len(s.out_toks):
                # budget exhausted by the first token (max_new=1):
                # the prefill result IS the final result — no handoff;
                # the engine retires it "length" and, with r.state
                # still "prefill", _on_prefill_complete surfaces it
                continue
            payload = eng.export_request(r.rid)
            r.state = "ready"
            self._ready.append((r.rid, payload))

    def _on_prefill_complete(self, c):
        """A prefill engine retired ``c``.  Handed-off requests retire
        with reason 'length' after their single budgeted token — that
        is the expected lifecycle event, swallowed here; the same echo
        arrives one tick late for a request the coordinator already
        requeued ('pending', worker-lost) or finalized ('done',
        parked-timeout), and must ALSO be swallowed or step() would
        surface a spurious truncated duplicate.  Everything else
        (first-token eos, single-token-budget 'length', failures,
        engine-side timeouts of an active prefill) is final."""
        r = self._reqs.get(c.request_id)
        if r is None:
            return c
        if r.state == "done":
            return None        # coordinator already finalized this rid
        if c.finish_reason == "length" and r.state in ("ready",
                                                       "decode",
                                                       "pending"):
            return None        # handoff in flight / requeue draining
        self._mark_done(r)
        return c

    def _deliver_ready(self):
        kept = deque()
        try:
            self._deliver_ready_inner(kept)
        finally:
            # exception-safe: a ship() that exhausts its retries must
            # not strand the payloads already parked in ``kept`` (nor
            # the ones still unprocessed) — recombine before the error
            # propagates so a caller that keeps stepping retries them
            kept.extend(self._ready)
            self._ready = kept

    def _deliver_ready_inner(self, kept):
        while self._ready:
            rid, payload = self._ready.popleft()
            r = self._reqs[rid]
            if r.state == "done":
                continue       # finalized elsewhere: drop the payload
            rem = self._remaining_ms(r)
            if rem is not None and rem <= 0:
                # expired while parked in the handoff queue — a window
                # neither engine's sweep covers
                self._timeout(r, payload["done_toks"])
                continue
            if faults.check(SITE_DECODE_WORKER_LOST, key=str(rid)):
                # decode worker died before the ack: the payload is
                # gone with it — requeue for a from-scratch re-prefill
                # (bitwise: greedy prefill+decode is deterministic)
                self._c_requeues.inc()
                r.state = "pending"
                r.requeues += 1
                self._pending.append(rid)
                _events.emit("serving.handoff_worker_lost", rid=rid)
                continue
            eng = self.decode_group[self._rr % len(self.decode_group)]
            self._rr += 1
            if self.transport.to is None and not any(
                    s.req is None for s in eng._slots):
                # no free slot on the (local) target: don't serialize
                # a multi-page payload just to have import refuse it —
                # park and retry next tick (import_request still
                # re-checks, covering page pressure; an rpc worker's
                # capacity is only knowable by asking, so that path
                # ships regardless)
                kept.append((rid, payload))
                continue

            def _on_retry(_exc, _n):
                self._c_retries.inc()

            t0 = time.perf_counter()
            with _tracing.span("serving.handoff", rid=str(rid),
                               pages=int(payload["n_pages"])):
                # stall watchdog (ISSUE 14): a wedged transfer past
                # the deadline gets thread stacks + a flight record
                # (no interrupt — the payload stays parked and the
                # next tick retries the handoff)
                wd = _watchdog.arm(
                    "serving.handoff",
                    float(_get_flag("watchdog_stall_ms")),
                    key=str(rid))
                try:
                    got, nbytes = self.transport.ship(
                        payload, eng, r.max_new_tokens,
                        deadline_ms=rem, on_retry=_on_retry)
                except Exception:
                    # retries exhausted (or a non-transient transport
                    # error): keep the payload so the next step()
                    # retries the handoff instead of stranding the rid
                    kept.append((rid, payload))
                    raise
                finally:
                    wd.disarm()
                ms = (time.perf_counter() - t0) * 1e3
                if got is None:
                    kept.append((rid, payload))   # no capacity yet
                    continue
                r.state = "decode"
                self._c_handoffs.inc()
                self._c_bytes.inc(nbytes)
                self._h_handoff.observe(ms)
                _events.emit("serving.handoff", rid=rid,
                             bytes=int(nbytes), ms=round(ms, 3),
                             pages=int(payload["n_pages"]))
