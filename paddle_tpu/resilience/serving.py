"""Serving-side resilience: the decode guard and the serving fault
sites (ISSUE 5).

The training stack guards a step with :class:`resilience.StepGuard` —
an in-graph finite-ness predicate that makes a bad step a bitwise
no-op. Serving needs the per-REQUEST analog: one request whose logits
go non-finite (bad weights region, poisoned KV, an injected drill)
must fail alone, never the engine or its co-resident requests. The
pieces here are model-agnostic and host-side; the in-graph half
(:func:`models.generation.guarded_argmax`) rides inside the engine's
compiled mixed/decode programs as a device-side flag, so detection
costs no extra host sync.

Serving fault sites (``resilience.faults`` spec grammar):

* ``engine_dispatch``      — raises ``InjectedConnectionError`` at the
  top of an engine dispatch; absorbed by the bounded retry every
  dispatch runs under. Key = dispatch kind (``mixed``/``decode``/
  ``window``).
* ``engine_nan_decode``    — poisons ONE slot's logits with NaN for
  one dispatch (host-built poison vector, added in-graph), drilling
  the decode guard. Key = the request id.
* ``engine_page_pressure`` — makes the page allocator behave as if
  the free list were empty for one growth attempt, drilling
  preempt-and-requeue without shrinking the pool. Key = the request
  id of the slot being grown.
* ``engine_cache_evict`` — forces the prefix cache
  (``inference/prefix_cache.py``) to evict its LRU cached page on one
  allocation even while free pages remain, drilling eviction-then-
  transparent-re-prefill without filling the pool. Key = the request
  id the allocation serves.
* ``engine_draft_nan`` — poisons ONE slot's speculative VERIFY rows
  with NaN for one dispatch (ISSUE 9): the per-draft guard
  (``models.generation.verify_argmax``) fails exactly that request
  with PDT-E018 while co-resident slots keep decoding. Key = the
  request id.
* ``engine_draft_mismatch`` — corrupts one slot's draft proposal
  (tokens shifted mod vocab) so the verify step rejects it, forcing
  the 0-accept path: outputs stay bitwise (the acceptance rule is
  correct for ANY drafts), only the accept rate moves. Key = the
  request id.
* ``engine_handoff_transient`` — one KV-page handoff transfer
  (``inference.distserve.KVPageTransport.ship``) raises
  ``InjectedConnectionError``; absorbed by the bounded
  ``resilience.retry`` every transfer runs under
  (``serving_disagg_handoff_retries``). Key = the request id.
* ``engine_decode_worker_lost`` — the decode worker is treated as
  dead at handoff time: the shipped payload is DISCARDED and the
  coordinator requeues the request to the prefill group, which
  re-prefills it from token zero — outputs stay bitwise (greedy
  prefill+decode is deterministic), only ``requeues`` moves. Key =
  the request id.
* ``engine_stall`` — one engine dispatch HANGS (a bounded Python
  spin standing in for a device that stopped answering), drilling
  the stall
  watchdog (``observability/watchdog.py``): past ``watchdog_ms`` the
  watchdog captures thread stacks, dumps the flight record + Chrome
  trace and injects ``EngineStallError`` (PDT-E020) into the spinning
  dispatch, which surfaces coded from ``step()`` — co-resident
  requests then complete bitwise on the re-dispatched plan. Key =
  dispatch kind (``mixed``/``decode``/``window``/``verify``).
* ``router_replica_lost`` — one fleet replica
  (``inference.router.FleetRouter``) is declared dead mid-decode:
  its queued AND in-flight requests requeue to the surviving
  replicas, which re-prefill them from token zero (restoring from
  their own prefix caches where pages match) — outputs stay bitwise
  (greedy decode is deterministic and batch-invariant), only
  ``requeues``/``deaths`` move and exactly one coded flight record
  (``ReplicaLostError`` PDT-E024) is written. Key = the replica
  name.
* ``router_dispatch_transient`` — one router->replica placement
  dispatch raises ``InjectedConnectionError``; absorbed by the
  bounded ``resilience.retry`` every placement runs under
  (``serving_fleet_dispatch_retries``), only the router ``retries``
  counter moves. Exhausting the retry budget is treated as a dead
  replica (the request requeues, the replica is killed). Key = the
  request id.
* ``router_scaleout_stall`` — one standby-replica admission
  (SLO-breach scale-out) HANGS, drilling the scale-out watchdog:
  past ``serving_fleet_scaleout_timeout_ms`` the admission surfaces
  ``EngineStallError`` (PDT-E020) with a flight record and the fleet
  DEGRADES GRACEFULLY — the standby stays parked and the live
  replicas keep serving. Key = the standby replica name.
* ``router_migration_transient`` — one live-migration snapshot
  transfer (``inference.distserve.KVPageTransport.ship_snapshot``,
  ISSUE 20) raises ``InjectedConnectionError``; absorbed by the
  bounded ``resilience.retry`` every transfer runs under
  (``serving_migration_retries``), only ``migration_retries`` moves.
  Exhausting the budget writes exactly one ``MigrationError``
  (PDT-E025) flight record and falls back to the PR17 COLD requeue:
  the source discards the resident silently, the request re-prefills
  front-of-line on a survivor — outputs stay bitwise (greedy decode
  is deterministic), demand is counted once. Key = the request id.
* ``engine_snapshot_torn`` — one migration payload arrives TORN at
  the destination (a byte of its KV pool bytes flipped in flight):
  ``restore_request`` rejects it on CRC validation with
  ``MigrationError`` (PDT-E025) and the SOURCE keeps the request —
  it stays resident and keeps decoding there, bitwise; only
  ``migration_failures`` moves. Key = the request id.
"""
from __future__ import annotations

import numpy as np

from ..core.errors import NonFiniteLogitsError
from . import faults

__all__ = [
    "FINISH_REASONS", "DecodeGuard", "dispatch_retry",
    "simulated_stall",
    "SITE_DISPATCH", "SITE_NAN_DECODE", "SITE_PAGE_PRESSURE",
    "SITE_CACHE_EVICT", "SITE_DRAFT_NAN", "SITE_DRAFT_MISMATCH",
    "SITE_HANDOFF_TRANSIENT", "SITE_DECODE_WORKER_LOST",
    "SITE_STALL", "SITE_ROUTER_REPLICA_LOST",
    "SITE_ROUTER_DISPATCH_TRANSIENT", "SITE_ROUTER_SCALEOUT_STALL",
    "SITE_MIGRATION_TRANSIENT", "SITE_SNAPSHOT_TORN",
]

#: Every value ``CompletedRequest.finish_reason`` can take.
FINISH_REASONS = ("stop", "length", "timeout", "cancelled", "failed")

SITE_DISPATCH = "engine_dispatch"
SITE_NAN_DECODE = "engine_nan_decode"
SITE_PAGE_PRESSURE = "engine_page_pressure"
SITE_CACHE_EVICT = "engine_cache_evict"
SITE_DRAFT_NAN = "engine_draft_nan"
SITE_DRAFT_MISMATCH = "engine_draft_mismatch"
SITE_HANDOFF_TRANSIENT = "engine_handoff_transient"
SITE_DECODE_WORKER_LOST = "engine_decode_worker_lost"
SITE_STALL = "engine_stall"
SITE_ROUTER_REPLICA_LOST = "router_replica_lost"
SITE_ROUTER_DISPATCH_TRANSIENT = "router_dispatch_transient"
SITE_ROUTER_SCALEOUT_STALL = "router_scaleout_stall"
SITE_MIGRATION_TRANSIENT = "router_migration_transient"
SITE_SNAPSHOT_TORN = "engine_snapshot_torn"


def simulated_stall(key: str, max_s: float = 30.0, site: str = SITE_STALL):
    """The ``engine_stall`` drill body: when the site fires, spin in
    Python (interpreter-visible, so the watchdog's injected
    ``EngineStallError`` lands at the next bytecode boundary — a real
    wedged C call could only be stack-dumped).  The spin is BOUNDED:
    with no watchdog armed the drill raises after ``max_s`` instead of
    hanging tier-1, which is the exact failure mode the watchdog
    exists to prevent.  ``site`` lets the other stall drills
    (``router_scaleout_stall``) reuse the same body."""
    import time as _time
    if not faults.check(site, key=str(key)):
        return
    t0 = _time.monotonic()
    while _time.monotonic() - t0 < max_s:
        _time.sleep(0.002)
    raise RuntimeError(
        f"{site} drill (key={key!r}): no watchdog interrupted "
        f"the stalled dispatch within {max_s}s — arm watchdog_ms / "
        "the watchdog_stall_ms flag when drilling this site")


class DecodeGuard:
    """Host half of the serving decode guard.

    Builds the per-slot poison vector each dispatch (NaN where the
    ``engine_nan_decode`` drill fires, else 0.0 — adding 0.0f to finite
    logits is argmax-invariant, so the guard is free when idle) and
    turns a device-reported bad flag into the coded error the engine
    records on the failed request.
    """

    def __init__(self, max_slots: int):
        self.max_slots = int(max_slots)

    def poison(self, slot_rids, sites=(SITE_NAN_DECODE,)) -> np.ndarray:
        """[max_slots] float32: NaN for slots whose request id fires
        one of the ``sites`` this dispatch, 0.0 elsewhere.
        ``slot_rids`` maps slot index -> request id (None = idle); the
        speculative verify dispatch adds ``engine_draft_nan`` so a
        NaN'd draft drills the per-draft guard."""
        vec = np.zeros(self.max_slots, np.float32)
        for b, rid in enumerate(slot_rids):
            if rid is None:
                continue
            for site in sites:
                if faults.check(site, key=str(rid)):
                    vec[b] = np.nan
                    # flight-recorder breadcrumb: the poison lands one
                    # dispatch before the guard reports it, so the
                    # drilled timeline reads cause -> effect like a
                    # real NaN would
                    from ..observability import events as _events
                    _events.emit("serving.nan_poison", rid=rid, slot=b,
                                 site=site)
                    break
        return vec

    @staticmethod
    def failure(rid, position) -> NonFiniteLogitsError:
        """The coded error recorded on a guard-failed request (never
        raised through the engine loop)."""
        return NonFiniteLogitsError(
            f"request {rid!r}: non-finite logits at position "
            f"{position} — decode guard failed this request only "
            f"[{NonFiniteLogitsError.error_code}]")


def dispatch_retry(kind: str, fn, *, max_attempts=3, on_retry=None):
    """Run one engine dispatch under bounded retry.

    The ``engine_dispatch`` fault check sits INSIDE the retried
    closure, so an injected transient is consumed per attempt and a
    ``*N``-spec drill is absorbed by ``N`` retries exactly like a real
    transient ConnectionError from a network-attached device. Delays
    are kept tiny: a serving step retried at human backoff scales
    would blow the latency budget before the second attempt.
    """
    from .retry import retry_call

    def call():
        faults.maybe_raise(SITE_DISPATCH, kind)
        simulated_stall(kind)
        return fn()

    return retry_call(call, max_attempts=max(1, int(max_attempts)),
                      base_delay=0.005, max_delay=0.05,
                      retry_on=(ConnectionError,), on_retry=on_retry)
