"""``paddle_tpu.observability`` — unified metrics + structured events.

A low-overhead, always-on observability runtime (ISSUE 8): one metrics
registry, one structured-event stream, one crash flight recorder —
instead of per-subsystem ``stats`` dicts and ad-hoc host timers.  The
``PDTPU_METRICS`` flag (``metrics`` in ``core/state.py``, on by
default) gates every record call; off makes each one a near-no-op and
restores pre-observability behavior bitwise (metrics backing the
serving engine's public ``stats`` contract are ``always=True`` and
record regardless).

Pieces
------
* ``metrics``   — thread-safe :class:`Counter`/:class:`Gauge`/
  :class:`Histogram` (fixed log-spaced buckets so snapshots merge
  elementwise) in process-global named registries
  (:func:`registry`); ``snapshot()`` nested JSON and a stable
  Prometheus text exporter (:func:`render_prometheus`).
* ``events``    — a bounded ring of recent structured events
  (:func:`emit`/:func:`tail`) fed by the serving engine, the resilience
  runtime (retries, StepGuard skips, fault firings, preemption
  signals) and the profiler; :func:`dump` writes the ring as a JSON
  flight record when a coded failure fires.
* ``serving``   — :class:`ServingTimelines` reconstructs per-request
  phase latencies (queue-time, TTFT, TPOT, decode-tokens-per-window,
  preemption / cache-hit histograms labeled by finish reason) from
  engine scheduling events — the ragged mixed program batches many
  requests into one dispatch, so host-side ``time.time()`` wrapping
  cannot attribute phases; the engine's own events can.
* ``steptimer`` — :class:`StepTimer` training telemetry (step wall
  histogram, retrace counter over ``Executable.trace_count``,
  tokens/sec + MFU estimate gauges, fused-optimizer bucket dispatch
  counter) hooked into ``hapi.Model.fit`` and ``Optimizer.step``;
  and, for the loop a user writes round a ``jit.to_static`` step, the
  window's timeline (ISSUE 40): three preallocated rings of
  ``perf_counter_ns`` marks, :func:`steptimer.call_log` (compiled
  calls), :func:`steptimer.read_log` (blocking host reads, with the
  process's rusage) and :func:`steptimer.gc_log` (collector pauses).
* ``tracing``   — distributed tracing (ISSUE 12): :func:`span` /
  :func:`traced` write ``span.begin``/``span.end`` pairs into the ring
  with a propagatable trace context (``trace_id``/``span_id``/
  ``parent_id``) carried through ``distributed/rpc`` calls
  (:class:`tracing.RemoteTraceContext`) and stamped onto the engine's
  dispatch events; :func:`export_trace` renders the ring — spans,
  serving lifecycle, fault/guard/retry events — as Chrome/Perfetto
  trace-event JSON, one track per rank/thread/engine slot.
* ``slo``       — SLO guardrails (ISSUE 14): declarative
  :class:`~paddle_tpu.observability.slo.SLOSpec` objectives (TTFT/TPOT/
  queue percentiles, goodput fraction, ``train.step_ms``) evaluated by
  :class:`~paddle_tpu.observability.slo.SLOEngine` over SLIDING WINDOWS
  of the existing histograms (cumulative-count deltas — nothing new on
  the hot path) with multi-window error-budget burn-rate alerting;
  breaches emit ``slo.breach`` and trigger a flight dump, and
  ``slo.budget_remaining`` / ``slo.burn_rate`` gauges ride
  ``render_prometheus``.  Armed on serving engines via the
  ``serving_slo`` flag / ``slo=`` kwarg (``engine.slo_status()``).
* ``watchdog``  — stall watchdog (ISSUE 14): daemon-thread heartbeat
  monitor armed around engine dispatches, DisaggServer handoffs, rpc
  invokes and ``Model.fit`` steps (``watchdog_stall_ms`` flag); past
  the deadline it captures every thread's stack, dumps the flight
  record + Chrome trace, emits ``watchdog.stall``, and (for the
  engine) injects a coded ``EngineStallError`` (PDT-E020) into the
  stalled dispatch instead of letting ``step()`` hang forever.
* ``aggregate`` — fleet-wide metrics (ISSUE 12):
  :func:`fleet_snapshot` publishes/gathers every rank's registry
  snapshot through the rendezvous ``TCPStore`` (straggler-tolerant
  timeout), merges elementwise (Counter sums, ``Histogram.merge``
  semantics, Gauges per-rank-labeled) and derives cross-rank skew —
  ``train.step_ms`` p50 spread, slowest-rank + slowest-phase
  attribution, ``overlap_frac`` per rank.

Event schema
------------
Every event is one flat JSON-able dict::

    {"seq": int, "ts": float, "kind": str, ...fields}

``seq`` is process-monotone, ``ts`` is ``time.time()``.  Kinds in use
(producers in parentheses; fields beyond rid/slot are scalars):

    serving.enqueued      rid, prompt_len, max_new_tokens   (engine)
    serving.admitted      rid, slot, cached_tokens, resume_len
    serving.prefill_chunk rid, slot, tokens, offset
    serving.first_token   rid, ttft_ms
    serving.decode_window tokens, live_slots
    serving.dispatch      name (mixed|decode|window|cow), ms
    serving.preempted     rid, tokens_done
    serving.retired       rid, finish_reason, tokens, preemptions
    serving.cache_evict   page, evictions              (prefix cache LRU)
    serving.nan_poison    rid, slot    (engine_nan_decode drill firing)
    retry.attempt         attempt, error, kind?         (resilience.retry)
    guard.step_skip       streak                        (StepGuard)
    fault.fired           site, key                     (faults.check)
    preempt.signal        signum                        (preempt handler)
    span                  name, dur_us   (profiler.RecordEvent: the
                          same code as tracing.span, Paddle's record)
    op                    name, dur_us                  (dispatch hook,
                                                         while profiling)
    flight.dump           reason, path                  (flight recorder)
    span.begin            name, span_id, trace_id, tname,
                          parent_id?, ...attrs          (tracing.span:
                          the one span API; every span is also a
                          jax.profiler annotation (a TraceMe), so it lies in a
                          live profiler session's .xplane.pb)
    span.end              name, span_id, trace_id, dur_us, error?
    spans of the program  (as span.begin/end) compile: fn, n_inputs,
                          n_state, n_donated (jit build); to_static.call
                          (fn, n: the call's number, its row's in
                          the call log) > to_static.read_state /
                          .launch / .write_state
                          (jit._Executable.__call__); tensor.readback
                          (a blocking host read of a tensor:
                          core/tensor.py Tensor._host); host.gc
                          (generation=2: a full collection, start to
                          stop; steptimer._gc_hook);
                          engine.step > engine.retire / .sweep / .admit
                          / .stage / serving.dispatch / engine.readback
                          (inference/engine.py); router.*, dp.*, pp.*,
                          collective.*, serving.handoff
    compile.retrace       fn, count, cause          (jit._Executable)
    rpc.client/rpc.server (as spans: fn, to/rank)   (distributed/rpc)
    slo.breach            slo, metric, value, target, burn_fast,
                          burn_slow                 (slo.SLOEngine)
    slo.recovered         slo, metric               (slo.SLOEngine)
    watchdog.stall        site, key, deadline_ms    (watchdog)

Rings of marks (``steptimer``; ``time.perf_counter_ns()``, int64 record
arrays in time order, empty while ``PDTPU_METRICS`` is off)::

    call_log()   n, fn, enter, read_state, launch, launched, done
                 last 4,096 compiled to_static calls; ``fn`` is the
                 compiled program's own index into call_fn_names();
                 four host phases a call: lookup, read_state, launch,
                 write_state
    read_log()   seq, begin, end, utime_ns, stime_ns, nivcsw, majflt
                 last 4,096 blocking reads of a device value; rusage
                 of the process as the read ends
    gc_log()     seq, begin, end, generation, collected
                 last 1,024 collections of generation 2 or of 1 ms
                 and more

Flight records are JSON files under ``PDTPU_FLIGHT_DIR`` (default
``<tempdir>/paddle_tpu_flight``); see ``events.dump``.  Flight-record
SCHEMA v2 (ISSUE 12): dumps carry ``schema_version`` plus ``rank`` /
``host`` identity fields so multi-rank dumps merge attributably; v1
records are identified by the ABSENCE of ``schema_version``.
``last_dump()`` semantics are unchanged.
"""
from __future__ import annotations

from . import events  # noqa: F401
from . import metrics  # noqa: F401
from .events import dump, dump_dir, emit, last_dump, tail  # noqa: F401
from .metrics import (COUNT_BUCKETS, LATENCY_BUCKETS_MS,  # noqa: F401
                      Counter, Gauge, Histogram, Registry, enabled,
                      registry, render_prometheus, snapshot)
from .serving import RegistryCounters, ServingTimelines  # noqa: F401
from .steptimer import StepTimer, device_peak_flops  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import (export_trace, render_trace, span,  # noqa: F401
                      traced)
from . import aggregate  # noqa: F401
from .aggregate import fleet_snapshot  # noqa: F401
from . import slo  # noqa: F401
from .slo import SLOEngine, SLOSpec, parse_slo  # noqa: F401
from . import watchdog  # noqa: F401

# events.dump is the flight recorder; keep a namespaced alias so call
# sites read as what they do: flight.dump(...)
from . import events as flight  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "registry",
    "snapshot", "render_prometheus", "enabled", "LATENCY_BUCKETS_MS",
    "COUNT_BUCKETS", "emit", "tail", "dump", "last_dump", "dump_dir",
    "flight", "events", "metrics", "ServingTimelines",
    "RegistryCounters", "StepTimer", "device_peak_flops",
    "tracing", "span", "traced", "export_trace", "render_trace",
    "aggregate", "fleet_snapshot",
    "slo", "SLOEngine", "SLOSpec", "parse_slo", "watchdog",
]
