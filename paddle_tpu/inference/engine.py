"""Continuous-batching serving engine over paged KV caches.

Capability analog of the request-level scheduling the reference's
``block_multi_head_attention`` kernel exists to serve
(``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``;
python surface ``incubate/nn/functional/block_multihead_attention.py``)
— the piece VERDICT r5 named as missing ("no request-level scheduler
that admits/retires sequences mid-decode").  Design follows the
Gemma-on-TPU serving study (arxiv 2605.25645, PAPERS.md): TPU serving
throughput comes from continuous batching over fixed-shape buckets.

Shape discipline (TPU-native):

* ONE page pool per layer ``[Hkv, total_pages, page_size, D]``; a
  free-list allocator hands pages to admitted requests and takes them
  back at retirement — HBM scales with resident tokens, not with
  ``max_slots * max_len``.  Page 0 is the reserved NULL page: inactive
  slots and packing padding write there, so retired block-table rows
  can never scribble a reassigned page.
* TWO compiled programs total, both bucket-stable:
  - the MIXED step (token budget T): prefill chunks of admitted
    requests packed together with one token from every ongoing decode —
    ``models.generation.ragged_paged_step`` serves both through one
    ragged kernel call.  Admission never stalls ongoing decodes, and a
    prompt longer than the budget prefills across consecutive steps
    (chunked prefill);
  - the DECODE window: ``decode_window`` steps scanned into one
    dispatch, slot state (tokens, positions, finished mask, page
    tables, KV pools) carried through the scan — one host round-trip
    per K tokens.
  Admission, retirement, preemption, cancellation and deadlines only
  change tensor VALUES (block tables, lengths, masks, the guard's
  poison vector) between dispatches — shapes never change, so no
  per-request recompiles.
* Greedy decoding (the serving bench's measurement mode); sampling
  belongs to ``models.generate``.

Overload behavior (ISSUE 5; the Gemma study and the Ragged Paged
Attention paper both treat admission under bounded HBM and
eviction/recompute of preempted sequences as first-class serving
mechanics):

* ON-DEMAND paging — admission reserves pages for the prompt plus one
  decode page only; block tables grow as decode crosses page
  boundaries.  Under pool pressure the allocator PREEMPTS a victim
  slot (latest-admitted first, never one admitted before the grower),
  returns its pages and requeues it at the queue head; re-admission
  re-prefills ``prompt + tokens_so_far``, which is bitwise-identical
  to an uncontended run (greedy decode is deterministic and the
  ragged prefill and decode paths agree bitwise — the engine-vs-
  generate parity tests pin that).  The earliest-admitted resident can
  always grow (eager admission bounds every request by the pool), so
  overload degrades throughput, never liveness.
* ADMISSION CONTROL — ``max_queue`` bounds the queue; policy
  ``reject`` raises :class:`~paddle_tpu.core.errors.QueueFullError`
  (PDT-E017), ``block`` steps the engine until room frees.  Requests
  that can NEVER fit the pool are rejected eagerly at ``add_request``
  with :class:`~paddle_tpu.core.errors.PageBudgetError` (PDT-E016).
* DEADLINES / CANCELLATION — per-request ``deadline_ms`` checked at
  step boundaries (``finish_reason == "timeout"``), ``cancel(rid)``
  for queued or resident requests (``"cancelled"``).
* DECODE GUARD — a device-side finite-ness flag over each slot's
  logits rides the mixed program and the decode-window scan carry
  (``models.generation.guarded_argmax``); a non-finite request fails
  ALONE (``finish_reason == "failed"``, coded
  ``NonFiniteLogitsError`` recorded on the result) while co-resident
  requests finish unperturbed.
* FAULT DRILLS — every dispatch runs under bounded
  ``resilience.retry``; the ``engine_dispatch`` / ``engine_nan_decode``
  / ``engine_page_pressure`` / ``engine_cache_evict`` sites
  (``resilience.serving``) drill the retry, guard, preemption and
  eviction paths deterministically.

Prefix caching (ISSUE 6; ``inference/prefix_cache.py``):

* CROSS-REQUEST KV REUSE — retirement and preemption PUBLISH a
  request's fully-written pages into a radix index keyed on
  page-granular token content instead of freeing them; admission walks
  the index and maps the matched prefix onto the existing pages
  (per-page refcounts pin shared pages while any resident uses them),
  so prefill starts at the first uncached token.  Because the ragged
  kernel treats block tables and lengths as data, a cache hit is
  purely a block-table indirection — outputs are bitwise-identical to
  the uncached engine and to ``generate(kv_cache='paged')``.
* COPY-ON-WRITE at the divergence page — a fully-cached (page-aligned)
  prompt still needs its last position's logits, so the last matched
  page is device-COPIED (one donated dispatch) and the one recomputed
  token writes to the private copy; every other admission starts
  prefill at a page boundary past the match, so shared pages are never
  write targets.
* LRU EVICTION — ref-0 cached pages are reclaimed least-recently-used
  (trie leaves first) before the allocator resorts to preemption;
  an evicted prefix transparently re-prefills.  Preempt-requeue
  re-admission hits the victim's own just-published pages, fixing the
  recompute gap: only ``tokens_since_last_full_page`` are re-prefilled
  instead of ``prompt + tokens_so_far``.
* The ``serving_prefix_cache`` flag (default on; ``off`` restores the
  uncached engine bitwise) / ``prefix_cache`` engine kwarg gate it;
  ``stats`` grows ``cache_hits`` / ``cache_hit_tokens`` /
  ``cached_pages`` / ``evictions`` and the prefill accounting pair
  ``prefill_tokens_requested`` / ``prefill_tokens_computed``.

Quantized KV (ISSUE 7; ``serving_kv_quant`` flag / ``kv_quant`` kwarg,
default off):

* INT8 PAGE POOLS — data pools store int8 and per-page scale
  side-pools ([Hk, P, page_size] f32, ``quantization.kv_quantize``)
  APPEND to the cache list; writes quantize inside
  ``models.generation.ragged_paged_step`` / ``paged_slot_attention``
  (each token's bytes a pure function of its own K/V vector — page
  content is write-path-independent), reads dequantize inside the
  ragged kernel, on the fetched pages.  KV bytes per resident sequence
  drop to ``(D + 4) / 4D`` of fp32 (< 0.5 for every real head dim;
  ``stats["kv_page_bytes"]``), which halves the HBM roofline term and
  doubles the sequences a fixed pool can hold.
* Because the scale pools ride the SAME block tables and page ids, the
  prefix cache (match, COW, publish, eviction), preempt-requeue and
  the decode-window donation all carry them transparently — no scale-
  aware branch exists anywhere in the scheduling layer.
* Greedy outputs are token-identical to the fp engine on the serving
  parity suite (int8 absmax per-vector error does not flip tiny-model
  argmax); with the flag off the engine is bitwise-identical to the
  pre-quantization fp path.

Observability (ISSUE 8; ``paddle_tpu.observability``):

* The engine's counters are RE-BACKED by a private metrics registry —
  ``stats`` keeps its exact pre-existing keys/values (always-on
  counters; the ``PDTPU_METRICS`` flag cannot zero the contract) while
  ``metrics()`` returns the full snapshot: the counters plus derived
  per-request timelines (queue-time, TTFT, TPOT,
  decode-tokens-per-window and per-dispatch latency histograms,
  finish-reason-labeled counters).  Phase attribution NEEDS engine
  events: prefill chunks and decodes share one ragged dispatch, so
  wrapping calls with host timers cannot tell requests apart.
* Scheduling emits structured events (enqueued / admitted /
  prefill_chunk / first_token / decode_window / preempted / retired,
  plus dispatch kinds) into the process event ring; coded failures —
  the decode guard's ``NonFiniteLogitsError``, a
  ``CacheIntegrityError`` page-conservation violation, the pool
  backstop — dump the ring as a JSON flight record
  (``PDTPU_FLIGHT_DIR``), so the postmortem starts from the last N
  events.  Clean runs dump nothing; ``PDTPU_METRICS=off`` restores
  the pre-observability engine bitwise.
* SLO GUARDRAILS & STALL WATCHDOG (ISSUE 14) — ``slo=`` arms
  declarative objectives (``observability/slo.py``) over the engine's
  own timeline histograms, evaluated at step boundaries over sliding
  windows with multi-window burn-rate alerting (``slo_status()``;
  breach -> ``slo.breach`` event + flight dump; budget gauges in
  ``render_prometheus()``); ``watchdog_ms=`` arms every dispatch with
  a stall deadline (``observability/watchdog.py``) past which thread
  stacks + the flight record + a Chrome trace are captured and a
  coded ``EngineStallError`` (PDT-E020) surfaces from ``step()``
  instead of a hang — drilled by the ``engine_stall`` fault site.
  Both are metrics-flag-gated no-ops when off.
* TRACING (ISSUE 12, ISSUE 26; ``observability/tracing.py``, the
  program's one span API) — a step runs under an ``engine.step`` span
  and its host time is split inside it: ``engine.retire``,
  ``engine.sweep``, ``engine.admit``, ``engine.stage`` (packing the
  step's host arrays and handing them to the device),
  ``serving.dispatch`` (the program call) and ``engine.readback`` (the
  tokens brought back).  While a profiler session is live each span is
  a profiler annotation on the device trace's clock; under
  ``PDTPU_METRICS`` its begin/end pair also lands in the event ring,
  and the timeline's ``serving.dispatch`` event carries the active
  ``trace_id``/``parent_id`` — a trace propagated in over
  ``distributed/rpc`` (disaggregated prefill/decode handoff) threads
  through to the dispatches that served it.
  ``observability.export_trace(path)`` renders the ring (lifecycle
  events per slot, spans, faults) as a Perfetto trace, one track per
  engine slot.

Speculative decoding (ISSUE 9; ``inference/speculative.py``,
``spec_decode`` kwarg / ``serving_spec_*`` flags, default off):

* DRAFT-PROPOSE / RAGGED-VERIFY — per decode step each slot submits
  its current token plus up to K proposed tokens as ONE ragged
  segment (``q_lens = K+1``) through the mixed program; the verify
  entry (``models.generation.verify_argmax``) returns the target's
  greedy pick after EVERY position, and the slot advances by the
  longest agreed draft prefix plus the target's free next token —
  1..K+1 tokens per dispatch instead of exactly one.  Greedy outputs
  are BITWISE-identical to ``spec_decode=off``: accepted tokens are by
  construction the tokens plain decode would have produced.
* RAGGED RETIREMENT / KV ROLLBACK — each slot's ``cur_pos`` /
  ``len_written`` advances by its own accept count; KV written past
  the first rejection is masked by ``kv_lens`` (data) and overwritten
  positionally by the next dispatch, so published prefix-cache pages
  only ever hold accepted tokens and ``kv_quant`` bytes for accepted
  positions are identical to the non-speculative path.
* PER-DRAFT GUARD — a slot whose verify segment contains any
  non-finite row fails ALONE (PDT-E018) while co-residents keep
  decoding; drilled by ``engine_draft_nan``, with
  ``engine_draft_mismatch`` forcing the rejection path (bitwise, only
  the accept rate moves).
* PROPOSERS — the model-free n-gram / prompt-lookup proposer (zero
  extra FLOPs, the serving-bench default) or a
  ``DraftModelProposer`` (small GPT/LLaMA with its OWN paged KV pool
  under the engine's free-list discipline).  ``stats`` grows
  ``spec_proposed`` / ``spec_accepted`` / ``spec_accept_rate``;
  timelines emit ``verify_window`` events and an
  accepted-tokens-per-step histogram.

Tensor parallelism & disaggregation (ISSUE 13; ``mesh=``/``tp_axis=``
kwargs / ``serving_tp`` flag; ``inference/distserve.py``):

* TP-SHARDED PROGRAMS — with a mesh, the mixed/spec/decode-window
  programs re-build over the TP axis (``models/generation.py`` TP
  section): weights column/row-split per the canonical Megatron rules
  (fused qkv re-laid-out head-major), KV data+scale pools sharded by
  kv-head (GQA-aware: ``Hk < tp`` replicates the K/V side and each
  shard attends a 1-head slice), block tables/lengths replicated, ONE
  psum at the attention output and the MLP reduce.  The scheduling
  layer is untouched — block tables and lengths are data either way —
  and greedy outputs are token-identical to the single-device engine
  (``tests/test_distserve.py``).
* POOL EXPORT/IMPORT — :meth:`export_request` serializes a resident
  request's live pages (+ scales) and scheduler state;
  :meth:`import_request` remaps them into this engine's free list
  (one compiled scatter per geometry; pages the prefix cache already
  indexes for the same token prefix are RETAINED instead of
  rewritten) and installs a decode slot.  ``DisaggServer`` builds the
  prefill->handoff->decode pipeline on top, with
  ``engine_handoff_transient`` / ``engine_decode_worker_lost`` drills
  and per-handoff spans/metrics.
* LIVE MIGRATION (ISSUE 20) — :meth:`snapshot_request` generalizes
  export to QUEUED and MID-PREFILL requests too (full scheduler
  state: tokens-so-far, cur_pos/prefill_off, deadline remaining,
  preemption/demand bookkeeping, a CRC over the KV bytes);
  :meth:`restore_request` CRC-validates the payload (a torn transfer
  is REJECTED with ``MigrationError`` PDT-E025 — the
  ``engine_snapshot_torn`` drill — and the source keeps the request),
  then funnels through the same import scatter / ``_release_slot``
  discipline; :meth:`discard_request` is the source's half of a
  completed migration — silently relinquish, no completion (unless a
  racing :meth:`cancel` owns the slot, in which case the source sweep
  finalizes it as "cancelled" and the destination drops its restore).
  A stream migrated mid-decode equals the unmigrated stream
  token-for-token: greedy decode is deterministic and batch-invariant
  and KV bytes are a pure function of the token prefix.
  ``FleetRouter`` drain / scale-in / lame-duck ride this
  (``inference/router.py``; ``serving_migration`` flag).

Compile-time program audit (ISSUE 16; ``analysis/program.py``):

* Every program the engine caches — the import scatter, COW page
  copy, decode windows, TP wrappers — is audited ONCE per (name,
  geometry) by the whole-program jaxpr analyzer at first compile
  (collective schedule consistency, donation/live-range HBM,
  recompile risk; see ``_audit_program``).  Gated by
  ``PDTPU_ANALYSIS`` (off = zero work) and never on the dispatch
  path.
"""
from __future__ import annotations

import time
import warnings
import zlib
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from ..core.errors import (CacheIntegrityError, EngineStallError,
                           MigrationError, PageBudgetError,
                           QueueFullError, UnimplementedError)
from ..core.tensor import Tensor
from ..observability import Registry as _ObsRegistry
from ..observability import flight as _flight
from ..observability import metrics as _obs_metrics
from ..observability import slo as _slo_mod
from ..observability import tracing as _tracing
from ..observability import watchdog as _watchdog
from ..observability.serving import RegistryCounters, ServingTimelines
from ..resilience import faults
from ..resilience.serving import (SITE_DRAFT_MISMATCH, SITE_DRAFT_NAN,
                                  SITE_PAGE_PRESSURE,
                                  SITE_SNAPSHOT_TORN, DecodeGuard,
                                  dispatch_retry)
from . import speculative as _spec
from .prefix_cache import PrefixCache

__all__ = ["ContinuousBatchingEngine", "CompletedRequest"]


class _Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_token_id",
                 "done_toks", "deadline", "preemptions",
                 "requested_counted")

    def __init__(self, rid, prompt, max_new_tokens, eos_token_id,
                 deadline=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token_id = eos_token_id
        self.done_toks: list[int] = []  # generated before a preemption
        self.deadline = deadline        # absolute clock() seconds | None
        self.preemptions = 0
        # prefill_tokens_requested counts each request's demand ONCE:
        # re-admissions (preempt resume, worker-lost / replica-lost
        # requeue via add_request(requeue=True)) must not re-count it,
        # or a shared-prefix/disagg report's prefill_saved_frac
        # denominator inflates with retry traffic while
        # prefill_tokens_computed keeps metering the actual recompute
        self.requested_counted = False


class CompletedRequest:
    """Result handed back by :meth:`ContinuousBatchingEngine.step`.

    ``finish_reason`` is one of ``resilience.serving.FINISH_REASONS``:
    ``stop`` (eos), ``length`` (max_new_tokens), ``timeout`` (deadline
    expired at a step boundary), ``cancelled`` (:meth:`cancel`), or
    ``failed`` (decode guard; the coded error is on ``error``).
    ``tokens`` holds whatever was generated before the cut."""

    __slots__ = ("request_id", "prompt", "tokens", "finish_reason",
                 "error")

    def __init__(self, request_id, prompt, tokens,
                 finish_reason="length", error=None):
        self.request_id = request_id
        self.prompt = prompt          # np.int32 [S]
        self.tokens = tokens          # np.int32 [<= max_new_tokens]
        self.finish_reason = finish_reason
        self.error = error            # coded exception for "failed"

    @property
    def ok(self):
        """True for a normally-finished request (stop/length)."""
        return self.finish_reason in ("stop", "length")

    @property
    def sequence(self):
        """prompt + generated tokens, the ``generate()``-comparable row."""
        return np.concatenate([self.prompt, self.tokens])


def _payload_crc(pools) -> int:
    """CRC32 over a migration payload's KV pool bytes (ISSUE 20) —
    computed at snapshot, validated at restore, so a torn transfer is
    rejected before any destination page is allocated."""
    crc = 0
    for arr in pools:
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return crc & 0xFFFFFFFF


class _Slot:
    __slots__ = ("req", "phase", "pages", "cur_tok", "cur_pos",
                 "prefill_ids", "prefill_off", "out_toks", "stop_len",
                 "eos", "admit_seq", "cancelled")

    def __init__(self):
        self.req = None
        self.phase = "free"           # free | prefill | decode
        self.pages = []
        self.cur_tok = 0
        self.cur_pos = 0
        self.prefill_ids = None       # prompt + replayed done_toks
        self.prefill_off = 0
        self.out_toks = []
        self.stop_len = 0
        self.eos = -1
        self.admit_seq = -1
        self.cancelled = False

    @property
    def len_written(self):
        """Tokens resident in the page pools (positions [0, len))."""
        if self.phase == "prefill":
            return self.prefill_off
        return self.cur_pos

    @property
    def done(self):
        if self.req is None:
            return True
        if self.phase == "prefill":
            return False
        if self.cur_pos + 1 >= self.stop_len:
            return True
        return bool(self.eos >= 0 and self.out_toks
                    and self.out_toks[-1] == self.eos)


class ContinuousBatchingEngine:
    """Request-level scheduler: ``add_request`` any time, ``step`` until
    it returns completions, or ``run`` to drain.  See the module
    docstring for the shape discipline and the overload policies.

    Policy knobs (engine kwargs; ``None`` falls back to the
    ``serving_*`` flags in ``core/state.py``): ``max_queue`` +
    ``queue_policy`` bound admission, ``default_deadline_ms`` applies a
    TTL to every request, ``dispatch_retries`` bounds the per-dispatch
    retry, ``prefix_cache`` gates the cross-request KV prefix cache
    (``serving_prefix_cache`` flag; ``False``/``'off'`` restores
    uncached admission bitwise), ``kv_quant`` stores KV pages int8
    with in-kernel dequant (``serving_kv_quant`` flag; default off =
    bitwise fp path), ``spec_decode``/``spec_k``/``spec_proposer``/
    ``spec_temperature``/``spec_rejection_sampling`` drive speculative
    decoding (``serving_spec_*`` flags; greedy spec is bitwise vs
    off), ``slo`` arms declarative latency/goodput objectives over
    the engine's own timelines (``serving_slo`` flag; spec string or
    ``SLOSpec`` list — see :meth:`slo_status`), ``watchdog_ms`` arms
    the stall watchdog around every dispatch (``watchdog_stall_ms``
    flag; a stalled dispatch surfaces ``EngineStallError`` PDT-E020
    with a flight record instead of hanging).  SIZE ``watchdog_ms``
    above the worst-case dispatch INCLUDING the first compile of each
    program geometry: a deadline under compile time interrupts the
    compile mid-flight, which never caches, so the next dispatch
    recompiles and stalls again — a livelock the deadline caused.
    Warm the geometry first (or arm after warmup) when tight
    deadlines matter.  ``clock`` (tests) replaces ``time.monotonic``
    for deterministic deadline drills."""

    def __init__(self, model, *, max_slots=8, page_size=16,
                 max_seq_len=None, total_pages=None, decode_window=8,
                 prefill_chunk=64, q_block=8, pages_per_block=None,
                 max_queue=None, queue_policy=None,
                 default_deadline_ms=None, dispatch_retries=None,
                 prefix_cache=None, kv_quant=None, spec_decode=None,
                 spec_k=None, spec_proposer=None, spec_temperature=None,
                 spec_rejection_sampling=None, spec_seed=0, clock=None,
                 mesh=None, tp_axis=None, slo=None, watchdog_ms=None):
        from ..core import state as _state
        from ..models.generation import (_decode_fn, _ragged_fn,
                                         _zero_pool)
        cfg = model.cfg
        self.model = model
        model.eval()   # the engine owns its model: serving is eval-mode
        self._decode, _, self._hard_limit = _decode_fn(model)
        self._ragged = _ragged_fn(model)
        # tensor parallelism (ISSUE 13): shard the two compiled serving
        # programs over a mesh axis — weights column/row-split per the
        # canonical Megatron rules, KV pools sharded by kv-head, block
        # tables/lengths replicated; greedy outputs token-identical to
        # the single-device engine (models/generation.py TP section).
        # ``mesh=None`` with the ``serving_tp`` flag > 1 builds a
        # default 1-axis mesh over the first ``serving_tp`` devices.
        tp_deg = int(_state.get_flag("serving_tp"))
        if mesh is None and tp_deg > 1:
            import jax as _jax
            devs = _jax.devices()
            if len(devs) < tp_deg:
                raise ValueError(
                    f"serving_tp={tp_deg} but only {len(devs)} devices "
                    "are visible")
            from jax.sharding import Mesh as _Mesh
            mesh = _Mesh(np.asarray(devs[:tp_deg]), ("tp",))
        self._jmesh = None
        self.tp_axis = None
        self._tpp = None
        if mesh is not None:
            jmesh = getattr(mesh, "jmesh", mesh)   # ProcessMesh or Mesh
            if tp_axis is None:
                axes = tuple(jmesh.axis_names)
                if len(axes) == 1:
                    tp_axis = axes[0]
                elif "tp" in axes:
                    tp_axis = "tp"
                else:
                    raise ValueError(
                        f"mesh has axes {axes}: pass tp_axis= to pick "
                        "the tensor-parallel one")
            from ..models.generation import tp_shard_params
            # sharded param extraction is a read-only snapshot cached
            # ON the model per (devices, axis): prefill/decode worker
            # engines sharing one model share one copy of the shards
            key = (tuple(d.id for d in jmesh.devices.flat),
                   str(tp_axis))
            tcache = model.__dict__.setdefault("_tp_params_cache", {})
            tpp = tcache.get(key)
            if tpp is None:
                tpp = tp_shard_params(model, jmesh, tp_axis)
                tcache[key] = tpp
            self._jmesh = jmesh
            self.tp_axis = str(tp_axis)
            self._tpp = tpp
        self.tp = 1 if self._tpp is None else self._tpp.meta["tp"]
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self._hard_limit:
            self.max_seq_len = min(self.max_seq_len, cfg.max_seq_len)
        self.decode_window = int(decode_window)
        self.q_block = int(q_block)
        self.prefill_chunk = max(self.q_block, int(prefill_chunk))
        self.pages_per_block = pages_per_block
        # per-slot page-table width covers the engine's length cap
        self.np_per_seq = -(-self.max_seq_len // self.page_size)
        if total_pages is None:
            total_pages = 1 + self.max_slots * self.np_per_seq
        self.total_pages = int(total_pages)
        # speculative decoding (ISSUE 9; inference/speculative.py):
        # decode slots submit K drafts + the current token as one
        # ragged verify segment through the mixed program and advance
        # by the accepted length — greedy outputs bitwise-identical to
        # spec off, only tokens-per-dispatch moves
        sd = (_state.get_flag("serving_spec_decode")
              if spec_decode is None else spec_decode)
        self.spec_decode = bool(sd)
        self.spec_k = int(_state.get_flag("serving_spec_k")
                          if spec_k is None else spec_k)
        st_ = (_state.get_flag("serving_spec_temperature")
               if spec_temperature is None else spec_temperature)
        self.spec_temperature = float(st_)
        rs = (_state.get_flag("serving_spec_rejection_sampling")
              if spec_rejection_sampling is None
              else spec_rejection_sampling)
        self.spec_rejection_sampling = bool(rs)
        self._proposer = None
        self._spec_rng = np.random.default_rng(int(spec_seed))
        if self.spec_decode:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, "
                                 f"got {self.spec_k}")
            from .speculative import make_proposer
            self._proposer = make_proposer(
                _state.get_flag("serving_spec_proposer")
                if spec_proposer is None else spec_proposer)
            self._proposer.bind(self)
        # token budget of the mixed step: one q_block per slot (the
        # ongoing decodes; under spec_decode a slot's verify segment is
        # up to spec_k+1 rows, q_block-padded) + the prefill chunk
        seg_rows = self.q_block
        if self.spec_decode:
            seg_rows = max(seg_rows, -(-(self.spec_k + 1)
                                       // self.q_block) * self.q_block)
        self.token_budget = (self.max_slots * seg_rows
                             + self.prefill_chunk)

        # overload policies (kwarg > flag; 0 flag values mean "off")
        self.max_queue = int(_state.get_flag("serving_max_queue")
                             if max_queue is None else max_queue)
        self.queue_policy = str(_state.get_flag("serving_queue_policy")
                                if queue_policy is None else queue_policy)
        if self.queue_policy not in ("reject", "block"):
            raise ValueError(
                f"queue_policy must be 'reject' or 'block', "
                f"got {self.queue_policy!r}")
        dl = float(_state.get_flag("serving_deadline_ms")
                   if default_deadline_ms is None else default_deadline_ms)
        self.default_deadline_ms = dl if dl > 0 else None
        self.dispatch_retries = int(
            _state.get_flag("serving_dispatch_retries")
            if dispatch_retries is None else dispatch_retries)
        self._clock = time.monotonic if clock is None else clock
        self._guard = DecodeGuard(self.max_slots)

        kq = (_state.get_flag("serving_kv_quant")
              if kv_quant is None else kv_quant)
        if isinstance(kq, str):
            # strict parse: kv_quant changes numerics, so a typo must
            # not silently enable lossy int8 KV
            if kq.lower() in _state.KV_QUANT_ON_SPELLINGS:
                kq = True
            elif kq.lower() in _state.KV_QUANT_OFF_SPELLINGS:
                kq = False
            else:
                raise ValueError(
                    f"kv_quant={kq!r}: expected one of "
                    f"{_state.KV_QUANT_ON_SPELLINGS} or "
                    f"{_state.KV_QUANT_OFF_SPELLINGS}")
        self.kv_quant = bool(kq)
        from ..ops import pallas as _pallas
        if not _pallas.use_interpret():
            for name in _pallas.TPU_REFUSED:
                if getattr(self, name):
                    raise UnimplementedError(
                        f"ContinuousBatchingEngine({name}=True) on a "
                        f"TPU: its kernel does not compile there — "
                        f"{_pallas.TPU_REFUSED[name]} "
                        f"[{UnimplementedError.error_code}]")
        n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
        shape = (n_kv, self.total_pages, self.page_size, cfg.head_dim)
        # int8 KV (ISSUE 7): data pools go int8 and per-page scale
        # side-pools [Hk, P, page_size] APPEND to the cache list —
        # every downstream consumer (COW copy, decode-window donation,
        # program signatures) treats the list opaquely, and the model
        # forwards split it by length (models/generation._split_caches),
        # so block tables, the prefix cache and preempt-requeue carry
        # the scales without knowing they exist.
        kv_dtype = "int8" if self.kv_quant else "float32"
        pools = list(_zero_pool(shape, 2 * cfg.num_layers, kv_dtype))
        if self.kv_quant:
            pools += list(_zero_pool(shape[:3], 2 * cfg.num_layers,
                                     "float32"))
        if self._tpp is not None:
            # pools live sharded by kv-head over the TP axis (or fully
            # replicated on the GQA Hk < tp path) — page ids and block
            # tables are pool-wide either way
            import jax as _jax
            from jax.sharding import NamedSharding as _NS

            from ..models.generation import tp_cache_spec
            cspec = tp_cache_spec(self._tpp.meta, self.tp_axis)
            pools = [_jax.device_put(p, _NS(self._jmesh, cspec))
                     for p in pools]
        self._caches = [Tensor(a) for a in pools]
        # bytes per page across all layers (data + scales): the
        # serving-roofline accounting the quant path halves
        itemsize = 1 if self.kv_quant else 4
        self._page_bytes = 2 * cfg.num_layers * n_kv * self.page_size \
            * (cfg.head_dim * itemsize + (4 if self.kv_quant else 0))
        self._free_pages = deque(range(1, self.total_pages))  # 0 = null
        pc = (_state.get_flag("serving_prefix_cache")
              if prefix_cache is None else prefix_cache)
        if isinstance(pc, str):
            pc = pc.lower() not in _state.PREFIX_CACHE_OFF_SPELLINGS
        self.prefix_cache_enabled = bool(pc)
        self._cache = PrefixCache(self.page_size, self._free_pages,
                                  enabled=self.prefix_cache_enabled,
                                  total_pages=self.total_pages)
        self._bt = np.zeros((self.max_slots, self.np_per_seq), np.int32)
        self._slots = [_Slot() for _ in range(self.max_slots)]
        self._queue: deque[_Request] = deque()
        self._early: list[CompletedRequest] = []  # finalized off-dispatch
        self._next_rid = 0
        self._admit_counter = 0
        self._step_fn = None
        self._mixed_fn = None
        self._spec_fn = None
        self._cow_fn = None
        self._import_fn = None
        self._decode_exe = None
        # counters, RE-BACKED by a private observability registry
        # (ISSUE 8): the ``stats`` property reads the same keys/values
        # as the old plain dict (always=True counters — the stats
        # contract predates the metrics flag), while ``metrics()``
        # exposes them alongside the timeline histograms.  The registry
        # is per-engine so concurrent engines never alias counters.
        self._registry = _ObsRegistry("serving_engine")
        self._stats = RegistryCounters(self._registry, (
            "admitted", "retired", "steps", "mixed_steps",
            "decode_dispatches", "tokens_generated", "pages_allocated",
            "peak_pages_in_use", "preemptions", "timeouts", "cancelled",
            "failed", "rejected", "retries", "cache_hits",
            "cache_hit_tokens", "prefill_tokens_requested",
            "prefill_tokens_computed"))
        # speculative counters (ISSUE 9) live in their OWN block so the
        # stats property can APPEND them after every pre-existing key —
        # the stats contract is keys/order-stable, new keys at the end
        self._spec_stats = RegistryCounters(self._registry, (
            "spec_proposed", "spec_accepted"))
        # live migration (ISSUE 20) — own block, APPENDED after the
        # spec keys by the stats property for the same reason
        self._mig_stats = RegistryCounters(self._registry, (
            "migrated_in", "migrated_out"))
        # per-request serving timelines (queue/TTFT/TPOT histograms +
        # structured events for the flight recorder), on the engine's
        # deadline clock so tests can drive them deterministically
        self._tl = ServingTimelines(self._registry, clock=self._clock)
        # live gauges read LAZILY at snapshot time (no work per step)
        reg = self._registry
        reg.gauge("serving.pages_in_use").set_function(
            self._pages_in_use)
        reg.gauge("serving.pages_free").set_function(
            lambda: len(self._free_pages))
        reg.gauge("serving.cached_pages").set_function(
            lambda: self._cache.cached_pages)
        reg.gauge("serving.queue_depth").set_function(
            lambda: len(self._queue))
        reg.gauge("serving.kv_page_bytes").set_function(
            lambda: self._page_bytes)
        # SLO guardrails (ISSUE 14, observability/slo.py): declarative
        # objectives over this engine's OWN timeline histograms,
        # evaluated over sliding windows once per scheduling step
        # (throttled — one clock compare when the interval hasn't
        # elapsed).  A multi-window burn-rate breach emits slo.breach
        # and dumps a flight record.  The stall watchdog
        # (observability/watchdog.py) arms every dispatch when
        # watchdog_ms > 0: a dispatch past the deadline gets its
        # thread stacks + flight record captured and a coded
        # EngineStallError injected instead of hanging step() forever.
        wd_ms = float(_state.get_flag("watchdog_stall_ms")
                      if watchdog_ms is None else watchdog_ms)
        self.watchdog_ms = wd_ms if wd_ms > 0 else 0.0
        slo_cfg = (_state.get_flag("serving_slo") if slo is None
                   else slo)
        specs = _slo_mod.parse_slo(slo_cfg)
        self._slo = None
        if specs:
            self._slo = _slo_mod.SLOEngine(
                self._registry, specs, clock=self._clock,
                on_breach=self._on_slo_breach)

    # ------------------------------------------------------------ API --
    def _pages_in_use(self) -> int:
        """Pages held by resident slots: the usable pool minus free
        minus cached — ONE home for the formula (the stats property,
        the lazy gauge and peak tracking all read it here)."""
        return (self.total_pages - 1 - len(self._free_pages)
                - self._cache.cached_pages)

    @property
    def stats(self):
        """Health snapshot: the lifetime counters plus live gauges
        (``pages_in_use``/``pages_free``/``cached_pages``/
        ``queue_depth``).  ``pages_in_use + pages_free + cached_pages``
        always sums to the usable pool (``total_pages - 1``)."""
        d = self._stats.as_dict()
        d["cached_pages"] = self._cache.cached_pages
        d["evictions"] = self._cache.evictions
        d["pages_in_use"] = self._pages_in_use()
        d["pages_free"] = len(self._free_pages)
        d["queue_depth"] = len(self._queue)
        # KV byte accounting (ISSUE 7): per-page bytes across all
        # layers including int8 scale side-pools — the quant path's
        # halved-bytes acceptance gate reads these
        d["kv_quant"] = self.kv_quant
        d["kv_page_bytes"] = self._page_bytes
        d["kv_bytes_in_use"] = d["pages_in_use"] * self._page_bytes
        # speculative decoding (ISSUE 9) — APPENDED: every pre-existing
        # key keeps its position (the backward-compat test pins that)
        d["spec_proposed"] = self._spec_stats["spec_proposed"]
        d["spec_accepted"] = self._spec_stats["spec_accepted"]
        d["spec_accept_rate"] = round(
            d["spec_accepted"] / d["spec_proposed"], 4) \
            if d["spec_proposed"] else 0.0
        # live migration (ISSUE 20) — APPENDED after the spec keys
        d["migrated_in"] = self._mig_stats["migrated_in"]
        d["migrated_out"] = self._mig_stats["migrated_out"]
        return d

    def metrics(self) -> dict:
        """Full observability snapshot (nested JSON): every ``stats``
        counter plus the derived serving timelines — queue-time, TTFT,
        TPOT and decode-tokens-per-window histograms, finish-reason
        labeled counters, per-dispatch latency.  See
        ``paddle_tpu.observability`` for the snapshot format."""
        return self._registry.snapshot()

    def render_prometheus(self) -> str:
        """This engine's metrics in Prometheus text format (the SLO
        budget-remaining / burn-rate gauges included when SLOs are
        armed)."""
        return self._registry.render_prometheus()

    def slo_status(self) -> list:
        """Per-spec SLO status (``observability/slo.py``): name, the
        windowed value vs target, fast/slow burn rates, error budget
        remaining, and the multi-window ``breached`` verdict.  Empty
        when no SLOs are armed (``serving_slo`` flag / ``slo=`` kwarg)
        or metrics are off."""
        if self._slo is None:
            return []
        return self._slo.status()

    def _on_slo_breach(self, status):
        """Breach hook: the SLOEngine already emitted ``slo.breach``
        into the ring; dump the flight record so the postmortem holds
        the minutes that burned the budget."""
        _flight.dump("slo_breach", extra=dict(status))

    def add_request(self, prompt, max_new_tokens, eos_token_id=None,
                    request_id=None, deadline_ms=None, requeue=False):
        prompt = np.asarray(
            prompt.numpy() if isinstance(prompt, Tensor) else prompt,
            np.int32).reshape(-1)
        total = prompt.size + int(max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"request needs {total} tokens > engine max_seq_len "
                f"{self.max_seq_len}")
        # eager page-budget rejection: a request whose full length can
        # never fit the pool must fail HERE, not poison the queue and
        # crash step() after everything ahead of it drains
        need_full = -(-total // self.page_size)
        if need_full > self.total_pages - 1:
            self._stats["rejected"] += 1
            raise PageBudgetError(
                f"request needs {need_full} pages but the pool only has "
                f"{self.total_pages - 1}; raise total_pages or lower "
                f"max_new_tokens [{PageBudgetError.error_code}]")
        if self.max_queue and len(self._queue) >= self.max_queue:
            if self.queue_policy == "reject":
                self._stats["rejected"] += 1
                raise QueueFullError(
                    f"admission queue full ({self.max_queue}); shed load "
                    f"or use queue_policy='block' "
                    f"[{QueueFullError.error_code}]")
            # block: drive the engine until the queue drains one slot.
            # Admissible requests always drain (see module docstring),
            # so this terminates; the guard catches a wedged engine.
            for _ in range(1_000_000):
                if len(self._queue) < self.max_queue or not self.has_work:
                    break
                self._early.extend(self.step())
            else:
                raise RuntimeError("queue_policy='block': engine made no "
                                   "progress draining the queue")
        if request_id is None:
            rid = self._next_rid
            self._next_rid += 1
        else:
            rid = request_id
            if isinstance(rid, int):  # auto ids must never collide
                self._next_rid = max(self._next_rid, rid + 1)
            in_flight = {r.rid for r in self._queue} | {
                s.req.rid for s in self._slots if s.req is not None}
            if rid in in_flight:
                raise ValueError(f"request_id {rid!r} already in flight")
        dl_ms = (self.default_deadline_ms
                 if deadline_ms is None else float(deadline_ms))
        deadline = (self._clock() + dl_ms / 1e3) if dl_ms else None
        req = _Request(
            rid, prompt, max_new_tokens,
            -1 if eos_token_id is None else int(eos_token_id), deadline)
        # requeue=True: a coordinator re-submitting a request it
        # already counted (disagg worker-lost, fleet replica-lost) —
        # its demand is already in prefill_tokens_requested
        req.requested_counted = bool(requeue)
        self._queue.append(req)
        self._tl.enqueued(rid, prompt.size, max_new_tokens)
        return rid

    def cancel(self, rid) -> bool:
        """Cancel a queued or resident request; its CompletedRequest
        (``finish_reason == "cancelled"``, tokens generated so far)
        surfaces from the next :meth:`step`. False when ``rid`` is not
        in flight (already completed or unknown)."""
        for i, r in enumerate(self._queue):
            if r.rid == rid:
                del self._queue[i]
                self._stats["cancelled"] += 1
                self._early.append(CompletedRequest(
                    rid, r.prompt, np.asarray(r.done_toks, np.int32),
                    "cancelled"))
                self._tl.retired(rid, "cancelled", len(r.done_toks),
                                 r.preemptions)
                return True
        for s in self._slots:
            if s.req is not None and s.req.rid == rid and not s.cancelled:
                s.cancelled = True   # finalized at the next step boundary
                return True
        return False

    def pending_requests(self):
        """Request ids still in flight (resident slots, then queued) —
        what a budget-exhausted :meth:`run` leaves behind."""
        out = [s.req.rid for s in self._slots if s.req is not None]
        out.extend(r.rid for r in self._queue)
        return out

    def cached_prefix_tokens(self, ids) -> int:
        """Longest page-aligned prefix of ``ids`` already indexed in
        this engine's radix prefix cache, in TOKENS (0 with caching
        off) — the fleet router's affinity-placement query
        (``inference/router.py``): route a prompt to the replica whose
        trie already holds its prefix and admission maps those pages
        instead of recomputing them.  Read-only apart from refreshing
        the matched path's LRU recency; never changes outputs."""
        ids = np.asarray(
            ids.numpy() if isinstance(ids, Tensor) else ids,
            np.int32).reshape(-1)
        return len(self._cache.match(ids)) * self.page_size

    @property
    def has_work(self):
        return bool(self._queue) or bool(self._early) or any(
            s.req is not None for s in self._slots)

    def run(self, max_steps=10000):
        """Drain: step until every queued/resident request completes.
        Returns {request_id: CompletedRequest} in completion order.
        Warns (once) when ``max_steps`` is exhausted with requests
        still in flight — see :meth:`pending_requests`."""
        done = {}
        for _ in range(max_steps):
            if not self.has_work:
                break
            for c in self.step():
                done[c.request_id] = c
        for c in self._early:   # finalized after the last step ran
            done[c.request_id] = c
        self._early.clear()
        if self.has_work:
            pend = self.pending_requests()
            warnings.warn(
                f"ContinuousBatchingEngine.run: step budget "
                f"({max_steps}) exhausted with {len(pend)} request(s) "
                f"unfinished — engine.pending_requests() lists them; "
                f"raise max_steps or check admission (queue depth "
                f"{len(self._queue)})", RuntimeWarning, stacklevel=2)
        return done

    # ------------------------------------- pool export / import -------
    # the KV-page handoff substrate of disaggregated prefill/decode
    # serving (inference/distserve.py): export serializes ONLY a
    # request's live pages (+ scale side-pools) and its scheduler
    # state; import remaps them into this engine's free list and
    # installs a resident decode slot.  Export is read-only (the
    # source engine's publish-at-retire / prefix-cache discipline is
    # untouched); import allocates through the prefix cache, so pages
    # this engine already holds for the same token prefix are RETAINED
    # instead of rewritten — cached prefixes survive handoff.

    def export_request(self, rid):
        """Serialize a resident decode-phase request for handoff.
        Returns a payload dict (numpy KV bytes + state); the slot
        stays resident — the caller decides when it retires."""
        for s in self._slots:
            if s.req is not None and s.req.rid == rid:
                break
        else:
            raise KeyError(f"request {rid!r} is not resident")
        if s.phase != "decode":
            raise ValueError(
                f"request {rid!r} is still prefilling — export after "
                "its first token")
        n = s.len_written
        n_pages = -(-n // self.page_size)
        pages = np.asarray(s.pages[:n_pages], np.int64)
        pools = [np.asarray(c._read()[:, pages]) for c in self._caches]
        return {
            "rid": rid,
            "prompt": np.asarray(s.req.prompt, np.int32),
            "done_toks": [int(t) for t in s.out_toks],
            "cur_tok": int(s.cur_tok),
            "cur_pos": int(s.cur_pos),
            "eos": int(s.eos),
            "len_written": int(n),
            "n_pages": int(n_pages),
            "page_size": self.page_size,
            "kv_quant": self.kv_quant,
            "pools": pools,
        }

    def _get_import_fn(self):
        if self._import_fn is None:
            key = ("import", len(self._caches)) + self._geometry()
            cache = self._program_cache()
            self._import_fn = cache.get(key)
        if self._import_fn is None:
            n = len(self._caches)
            from ..models.generation import make_import_scatter
            shardings = None
            if self._tpp is not None:
                from jax.sharding import NamedSharding as _NS

                from ..models.generation import tp_cache_spec
                cspec = tp_cache_spec(self._tpp.meta, self.tp_axis)
                shardings = [_NS(self._jmesh, cspec)
                             for _ in range(n)]
            self._import_fn = make_import_scatter(n, shardings)
            self._program_cache()[("import", len(self._caches))
                                  + self._geometry()] = self._import_fn
        return self._import_fn

    def _scatter_payload(self, pages, n_matched, n_imp, pools):
        """Scatter a payload's FRESH page rows (payload slots
        ``[n_matched, n_imp)``; prefix-cache-matched pages already hold
        identical bytes) into the pool pages named by ``pages`` — ONE
        compiled dispatch per geometry (the page-id vector is traced
        data; idx/payload pad to the table width so one program serves
        every import/restore of this geometry).  On failure every page
        reference in ``pages`` is released before re-raising: no slot
        owns them yet, so the ``_release_slot`` funnel could never
        return them and each caller retry would leak ``n_alloc``
        pages otherwise."""
        NP = self.np_per_seq
        idx = np.zeros(NP, np.int32)
        sel = np.zeros(NP, np.int64)          # payload page slot -> row
        take = np.zeros(NP, bool)
        for j in range(n_matched, n_imp):
            idx[j] = pages[j]
            sel[j] = j
            take[j] = True
        if not take.any():   # a full prefix-cache hit scatters nothing
            return
        pads = []
        for arr in pools:
            pad = np.zeros(arr.shape[:1] + (NP,) + arr.shape[2:],
                           arr.dtype)
            pad[:, take] = arr[:, sel[take]]
            pads.append(pad)
        fn = self._get_import_fn()
        vals = [c._read() for c in self._caches]
        self._audit_program(
            "import", fn,
            (jnp.asarray(idx), *vals,
             *[jnp.asarray(p) for p in pads]),
            donated=tuple(range(1, 1 + len(vals))))

        def _import_call():
            if any(getattr(v, "is_deleted", lambda: False)()
                   for v in vals):
                raise RuntimeError(
                    "import dispatch failed after its KV buffers "
                    "were donated; a mid-execution transient is "
                    "unrecoverable at this layer — re-create the "
                    "engine and re-submit the pending requests")
            return fn(jnp.asarray(idx), *vals,
                      *[jnp.asarray(p) for p in pads])

        try:
            new = self._dispatch("import", _import_call)
        except Exception:
            self._cache.release(pages)
            raise
        for t, v in zip(self._caches, new):
            t._data = v
            t._node = None

    def import_request(self, payload, max_new_tokens, request_id=None,
                       deadline_ms=None):
        """Install an exported (prefilled) request as a resident
        DECODE slot: allocate pages, scatter the payload's KV bytes
        into them (ONE compiled dispatch per geometry; the page-id
        vector is traced data), and seed the slot's scheduler state.
        Pages this engine's prefix cache already indexes for the same
        token prefix are retained instead of scattered.  Returns the
        request id, or ``None`` when no slot / not enough pages are
        free right now (retry after a step)."""
        if payload["page_size"] != self.page_size \
                or payload["kv_quant"] != self.kv_quant \
                or len(payload["pools"]) != len(self._caches):
            raise ValueError(
                "import_request: incompatible KV layout (page_size/"
                "kv_quant/pool count must match the exporting engine)")
        prompt = np.asarray(payload["prompt"], np.int32)
        done = list(payload["done_toks"])
        cur_pos = int(payload["cur_pos"])
        stop = prompt.size + int(max_new_tokens)
        if stop > self.max_seq_len:
            raise ValueError(
                f"request needs {stop} tokens > engine max_seq_len "
                f"{self.max_seq_len}")
        if len(done) >= int(max_new_tokens):
            raise ValueError(
                "import_request: request already complete — finalize "
                "it on the coordinator instead of importing")
        rid = payload["rid"] if request_id is None else request_id
        if isinstance(rid, int):   # keep add_request's auto ids clear
            self._next_rid = max(self._next_rid, rid + 1)
        in_flight = {r.rid for r in self._queue} | {
            s.req.rid for s in self._slots if s.req is not None}
        if rid in in_flight:
            raise ValueError(f"request_id {rid!r} already in flight")
        need_full = -(-stop // self.page_size)
        if need_full > self.total_pages - 1:
            self._stats["rejected"] += 1
            raise PageBudgetError(
                f"request needs {need_full} pages but the pool only "
                f"has {self.total_pages - 1} "
                f"[{PageBudgetError.error_code}]")
        for b, s in enumerate(self._slots):
            if s.req is None:
                break
        else:
            return None                       # no free slot: retry
        n_imp = int(payload["n_pages"])
        ps = self.page_size
        target = max(cur_pos, min(cur_pos + 1, stop))
        n_need = max(n_imp, max(1, -(-target // ps)))
        # decode-side prefix reuse: full pages this engine already
        # indexes for the written token prefix ride as-is (the bytes
        # are identical by construction — KV content is a pure
        # function of the token prefix)
        ids_written = np.concatenate(
            [prompt, np.asarray(done, np.int32)])[:cur_pos]
        matched = self._cache.match(ids_written)[:n_imp]
        self._cache.retain(matched)
        n_alloc = n_need - len(matched)
        if n_alloc > self._cache.available():
            self._cache.release(matched)
            return None                       # pool pressure: retry
        alloc = [self._cache.acquire(key=str(rid))
                 for _ in range(n_alloc)]
        pages = matched + alloc
        self._scatter_payload(pages, len(matched), n_imp,
                              payload["pools"])
        req = _Request(rid, prompt, int(max_new_tokens),
                       int(payload["eos"]),
                       (self._clock() + float(deadline_ms) / 1e3)
                       if deadline_ms else None)
        s.req = req
        s.phase = "decode"
        s.pages = pages
        s.out_toks = done
        s.cur_tok = int(payload["cur_tok"])
        s.cur_pos = cur_pos
        s.stop_len = stop
        s.eos = int(payload["eos"])
        s.admit_seq = self._admit_counter
        self._admit_counter += 1
        self._bt[b, :] = 0
        self._bt[b, :len(pages)] = pages
        self._stats["admitted"] += 1
        self._stats["pages_allocated"] += len(alloc)
        if matched:
            self._stats["cache_hits"] += 1
            self._stats["cache_hit_tokens"] += len(matched) * ps
        self._tl.enqueued(rid, prompt.size, int(max_new_tokens))
        self._tl.admitted(rid, b, cached_tokens=len(matched) * ps,
                          resume_len=cur_pos)
        for _ in done:      # tokens the prefill side already produced
            self._tl.token(rid)
        self._note_peak()
        return rid

    # ------------------------------------------- live migration -------
    # ISSUE 20: the full-request snapshot/restore/discard triple the
    # fleet router's drain / scale-in / lame-duck paths ride.  Snapshot
    # generalizes export to queued and mid-prefill requests (full
    # scheduler state + a CRC over the KV bytes); restore validates and
    # funnels through the import scatter; discard is the source's half
    # of a completed migration — silent, no CompletedRequest, and it
    # DEFERS to a racing cancel() (the sweep owns cancelled slots).

    def _snapshot_state(self, req, phase):
        rem = None
        if req.deadline is not None:
            rem = (req.deadline - self._clock()) * 1e3
        return {
            "kind": "snapshot",
            "version": 1,
            "phase": phase,
            "rid": req.rid,
            "prompt": np.asarray(req.prompt, np.int32),
            "max_new_tokens": int(req.max_new_tokens),
            "eos": int(req.eos_token_id),
            "deadline_ms": rem,
            "preemptions": int(req.preemptions),
            "requested_counted": bool(req.requested_counted),
            "page_size": self.page_size,
            "kv_quant": self.kv_quant,
        }

    def snapshot_request(self, rid):
        """Serialize a QUEUED or RESIDENT request for live migration
        (ISSUE 20).  The payload extends :meth:`export_request` with
        the full scheduler state — phase, tokens-so-far, deadline
        REMAINING (absolute deadlines don't survive a clock change of
        engine), preemption/demand bookkeeping — plus a CRC over the
        KV pool bytes so :meth:`restore_request` rejects a torn
        transfer.  Queued requests carry no pools; mid-prefill
        residents carry the pages written so far (``prefill_off``
        positions), so a planned preemption loses zero prefill work.
        The request stays here untouched — the caller pairs a
        successful restore with :meth:`discard_request`.  Raises
        ``KeyError`` when ``rid`` is not in flight and ``ValueError``
        for a slot migration must skip (cancelled: the sweep owns it;
        done: it retires on the next step)."""
        for r in self._queue:
            if r.rid == rid:
                p = self._snapshot_state(r, "queued")
                p.update(done_toks=[int(t) for t in r.done_toks],
                         len_written=0, n_pages=0, pools=[],
                         crc=_payload_crc([]))
                return p
        for s in self._slots:
            if s.req is not None and s.req.rid == rid:
                break
        else:
            raise KeyError(f"request {rid!r} is not queued or resident")
        if s.cancelled:
            raise ValueError(
                f"request {rid!r} is cancelled — the sweep finalizes "
                "it on this engine; migration must skip it")
        if s.phase == "decode" and s.done:
            raise ValueError(
                f"request {rid!r} is complete — it retires on the "
                "next step; migration must skip it")
        n = s.len_written
        n_pages = -(-n // self.page_size)
        pages = np.asarray(s.pages[:n_pages], np.int64)
        pools = [np.asarray(c._read()[:, pages]) for c in self._caches]
        p = self._snapshot_state(s.req, s.phase)
        p.update(done_toks=[int(t) for t in s.out_toks],
                 cur_tok=int(s.cur_tok), cur_pos=int(s.cur_pos),
                 len_written=int(n), n_pages=int(n_pages),
                 pools=pools, crc=_payload_crc(pools))
        if s.phase == "prefill":
            p["prefill_off"] = int(s.prefill_off)
        return p

    def restore_request(self, payload, max_new_tokens=None,
                        request_id=None, deadline_ms=None):
        """Install a migrated :meth:`snapshot_request` payload —
        queued payloads re-enter admission (demand already counted on
        the source rides the ``requeue`` contract), resident payloads
        funnel through the import scatter and land a slot in the
        SAME phase at the same position, so the continued stream is
        bitwise the unmigrated one.  The payload CRC is validated
        first: a torn transfer (``engine_snapshot_torn`` drill) raises
        ``MigrationError`` (PDT-E025) before any page is allocated and
        the source keeps the request.  Returns the request id, or
        ``None`` when no slot / not enough pages are free right now
        (retry after a step); raises ``ValueError`` for a payload
        whose source cancelled it (the destination drops the
        restore)."""
        phase = payload.get("phase", "decode")
        rid = payload["rid"] if request_id is None else request_id
        if payload.get("cancelled"):
            raise ValueError(
                f"request {rid!r} was cancelled on the source — "
                "dropping the restore (the source sweep finalizes it)")
        pools = list(payload.get("pools") or [])
        if pools and faults.check(SITE_SNAPSHOT_TORN, key=str(rid)):
            # drill: the transfer tore mid-flight — flip one KV byte
            # on a local copy so CRC validation catches it below
            torn = np.array(pools[0], copy=True)
            if torn.nbytes:
                torn.view(np.uint8).reshape(-1)[0] ^= 0xFF
            pools[0] = torn
        crc = payload.get("crc")
        if crc is not None and _payload_crc(pools) != int(crc):
            raise MigrationError(
                f"restore_request: snapshot payload for request "
                f"{rid!r} failed CRC validation (torn transfer) — "
                f"restore rejected, the source keeps the request "
                f"[{MigrationError.error_code}]")
        mnt = int(payload["max_new_tokens"]
                  if max_new_tokens is None else max_new_tokens)
        if deadline_ms is None:
            deadline_ms = payload.get("deadline_ms")
        if phase == "queued":
            eos = int(payload["eos"])
            out = self.add_request(
                payload["prompt"], mnt, None if eos < 0 else eos,
                request_id=rid, deadline_ms=deadline_ms,
                requeue=bool(payload.get("requested_counted")))
            req = self._queue[-1]
            req.done_toks = [int(t) for t in payload.get("done_toks",
                                                         [])]
            req.preemptions = int(payload.get("preemptions", 0))
            self._mig_stats["migrated_in"] += 1
            self._tl.migrated(out, "in", phase="queued")
            return out
        if phase == "decode":
            pl = dict(payload)
            pl["pools"] = pools
            out = self.import_request(pl, mnt, request_id=request_id,
                                      deadline_ms=deadline_ms)
            if out is None:
                return None
            for s in self._slots:
                if s.req is not None and s.req.rid == out:
                    s.req.preemptions = int(
                        payload.get("preemptions", 0))
                    s.req.requested_counted = bool(
                        payload.get("requested_counted", True))
                    break
            self._mig_stats["migrated_in"] += 1
            self._tl.migrated(out, "in",
                              pages=int(payload.get("n_pages", 0)),
                              phase="decode")
            return out
        # phase == "prefill": land a MID-PREFILL resident — the pages
        # written so far ship warm; the destination's chunked prefill
        # resumes at prefill_off exactly (arbitrary offsets are normal
        # there: budget-limited chunks split mid-page already), so no
        # prefill work is recomputed and the stream stays bitwise
        if payload["page_size"] != self.page_size \
                or payload["kv_quant"] != self.kv_quant \
                or len(pools) != len(self._caches):
            raise ValueError(
                "restore_request: incompatible KV layout (page_size/"
                "kv_quant/pool count must match the source engine)")
        prompt = np.asarray(payload["prompt"], np.int32)
        done = [int(t) for t in payload["done_toks"]]
        off = int(payload["prefill_off"])
        stop = prompt.size + mnt
        if stop > self.max_seq_len:
            raise ValueError(
                f"request needs {stop} tokens > engine max_seq_len "
                f"{self.max_seq_len}")
        if isinstance(rid, int):
            self._next_rid = max(self._next_rid, rid + 1)
        in_flight = {r.rid for r in self._queue} | {
            s.req.rid for s in self._slots if s.req is not None}
        if rid in in_flight:
            raise ValueError(f"request_id {rid!r} already in flight")
        need_full = -(-stop // self.page_size)
        if need_full > self.total_pages - 1:
            self._stats["rejected"] += 1
            raise PageBudgetError(
                f"request needs {need_full} pages but the pool only "
                f"has {self.total_pages - 1} "
                f"[{PageBudgetError.error_code}]")
        for b, s in enumerate(self._slots):
            if s.req is None:
                break
        else:
            return None                       # no free slot: retry
        ps = self.page_size
        n_imp = int(payload["n_pages"])
        ids = (np.concatenate([prompt, np.asarray(done, np.int32)])
               if done else prompt)
        resume = int(ids.size)
        target = max(resume, min(resume + 1, stop))
        n_need = max(n_imp, max(1, -(-target // ps)))
        matched = self._cache.match(ids[:off])[:n_imp]
        self._cache.retain(matched)
        n_alloc = n_need - len(matched)
        if n_alloc > self._cache.available():
            self._cache.release(matched)
            return None                       # pool pressure: retry
        alloc = [self._cache.acquire(key=str(rid))
                 for _ in range(n_alloc)]
        pages = matched + alloc
        self._scatter_payload(pages, len(matched), n_imp, pools)
        req = _Request(rid, prompt, mnt, int(payload["eos"]),
                       (self._clock() + float(deadline_ms) / 1e3)
                       if deadline_ms else None)
        req.done_toks = done
        req.preemptions = int(payload.get("preemptions", 0))
        req.requested_counted = bool(
            payload.get("requested_counted", True))
        s.req = req
        s.phase = "prefill"
        s.pages = pages
        s.prefill_ids = ids
        s.prefill_off = off
        s.out_toks = list(done)
        s.stop_len = stop
        s.eos = int(payload["eos"])
        s.admit_seq = self._admit_counter
        self._admit_counter += 1
        self._bt[b, :] = 0
        self._bt[b, :len(pages)] = pages
        self._stats["admitted"] += 1
        self._stats["pages_allocated"] += len(alloc)
        if matched:
            self._stats["cache_hits"] += 1
            self._stats["cache_hit_tokens"] += len(matched) * ps
        self._mig_stats["migrated_in"] += 1
        self._tl.enqueued(rid, prompt.size, mnt)
        self._tl.admitted(rid, b, cached_tokens=len(matched) * ps,
                          resume_len=off)
        self._tl.migrated(rid, "in", pages=n_imp, phase="prefill")
        self._note_peak()
        return rid

    def discard_request(self, rid) -> bool:
        """Silently relinquish a queued or resident request — the
        SOURCE half of a completed live migration.  No
        CompletedRequest is emitted (the request lives on at the
        destination, whose retirement owns the finish reason); a
        resident's fully-written pages are published to the prefix
        cache first, then the slot funnels through
        :meth:`_release_slot` as always.  Returns ``False`` without
        touching anything when a racing :meth:`cancel` marked the
        slot: the sweep finalizes it as "cancelled" HERE — the caller
        must drop the destination's restore so exactly one side
        honors the cancel.  Raises ``KeyError`` when ``rid`` is not
        in flight."""
        for i, r in enumerate(self._queue):
            if r.rid == rid:
                del self._queue[i]
                self._mig_stats["migrated_out"] += 1
                self._tl.migrated(rid, "out", phase="queued")
                return True
        for b, s in enumerate(self._slots):
            if s.req is not None and s.req.rid == rid:
                if s.cancelled:
                    return False
                phase = s.phase
                n_pages = len(s.pages)
                self._publish_slot(b)
                self._release_slot(b)
                self._mig_stats["migrated_out"] += 1
                self._tl.migrated(rid, "out", pages=n_pages,
                                  phase=phase)
                return True
        raise KeyError(f"request {rid!r} is not queued or resident")

    # ------------------------------------------------- scheduling -----
    def _release_slot(self, b):
        """Free slot ``b``: pages drop their resident reference (the
        prefix cache routes them — ref-0 indexed pages stay CACHED for
        future admissions, the rest return to the free list), the
        block-table row is nulled (null page: a frozen slot's writes
        can never touch a reissued page), the slot reset.  The ONLY
        way pages leave a slot — every retire/finalize/preempt path
        funnels here."""
        s = self._slots[b]
        if self._proposer is not None and s.req is not None:
            # proposer state follows the page discipline: a slot that
            # drops its pages drops its draft KV too (a preempted
            # request's proposer re-prefills on re-admission)
            self._proposer.release(s.req.rid)
        self._cache.release(s.pages)
        self._bt[b, :] = 0
        self._slots[b] = _Slot()

    def _publish_slot(self, b):
        """Index slot ``b``'s fully-written pages in the prefix cache
        (partial tail pages stay private) so later admissions — and
        this request's OWN re-admission after a preemption — map the
        prefix instead of re-prefilling.  Must run before
        :meth:`_release_slot` reads the slot's state away."""
        s = self._slots[b]
        n = s.len_written
        if n < self.page_size:
            return
        if s.phase == "prefill":
            ids = s.prefill_ids[:n]
        else:
            ids = np.concatenate(
                [s.req.prompt,
                 np.asarray(s.out_toks, np.int32)])[:n]
        self._cache.publish(ids, s.pages, n)

    def _finalize_slot(self, b, reason, error=None):
        """Retire slot ``b`` off the normal path (timeout / cancelled /
        failed / preempt-to-nowhere): free its pages, null its block
        table row, emit the partial result."""
        s = self._slots[b]
        toks = np.asarray(s.out_toks[:s.req.max_new_tokens], np.int32)
        comp = CompletedRequest(s.req.rid, s.req.prompt, toks, reason,
                                error)
        if reason != "failed":  # a guard-failed slot's KV is suspect:
            self._publish_slot(b)  # never index poisoned pages
        self._tl.retired(s.req.rid, reason, int(toks.size),
                         s.req.preemptions)
        self._release_slot(b)
        return comp

    def _retire(self):
        out = []
        for b, s in enumerate(self._slots):
            if s.req is None or not s.done or s.cancelled:
                continue  # cancelled-but-done: _sweep finalizes it as
                          # "cancelled" (cancel() already promised so)
            toks = s.out_toks[:s.req.max_new_tokens]
            reason = "length"
            if s.eos >= 0 and s.eos in toks:
                toks = toks[:toks.index(s.eos) + 1]
                reason = "stop"
            out.append(CompletedRequest(
                s.req.rid, s.req.prompt, np.asarray(toks, np.int32),
                reason))
            self._publish_slot(b)
            self._tl.retired(s.req.rid, reason, len(toks),
                             s.req.preemptions)
            self._release_slot(b)
            self._stats["retired"] += 1
        return out

    def _sweep(self, now):
        """Step-boundary policy sweep: expire deadlines (queued AND
        resident) and finalize cancelled residents."""
        out = []
        if any(r.deadline is not None and now >= r.deadline
               for r in self._queue):
            kept = deque()
            for r in self._queue:
                if r.deadline is not None and now >= r.deadline:
                    self._stats["timeouts"] += 1
                    out.append(CompletedRequest(
                        r.rid, r.prompt,
                        np.asarray(r.done_toks, np.int32), "timeout"))
                    self._tl.retired(r.rid, "timeout",
                                     len(r.done_toks), r.preemptions)
                else:
                    kept.append(r)
            self._queue = kept
        for b, s in enumerate(self._slots):
            if s.req is None:
                continue
            if s.cancelled:
                self._stats["cancelled"] += 1
                out.append(self._finalize_slot(b, "cancelled"))
            elif s.req.deadline is not None and now >= s.req.deadline:
                self._stats["timeouts"] += 1
                out.append(self._finalize_slot(b, "timeout"))
        return out

    # --------------------------------------------- page allocation ----
    def _admit_need(self, req):
        """Pages an admission reserves: the (resume) prompt plus ONE
        decode slot — growth is on-demand from there."""
        resume = req.prompt.size + len(req.done_toks)
        stop = req.prompt.size + req.max_new_tokens
        target = max(resume, min(resume + 1, stop))
        return max(1, -(-target // self.page_size))

    def _note_peak(self):
        self._stats["peak_pages_in_use"] = max(
            self._stats["peak_pages_in_use"], self._pages_in_use())

    def _admit(self):
        for b, s in enumerate(self._slots):
            if s.req is not None or not self._queue:
                continue
            req = self._queue[0]
            # a preempted request resumes at prompt + tokens_so_far:
            # greedy decode is deterministic and the ragged prefill and
            # decode paths agree bitwise, so the resumed stream is
            # identical to the uncontended one
            if req.done_toks:
                resume_ids = np.concatenate(
                    [req.prompt, np.asarray(req.done_toks, np.int32)])
            else:
                resume_ids = req.prompt
            resume = int(resume_ids.size)
            stop = req.prompt.size + req.max_new_tokens
            need_total = self._admit_need(req)
            # radix walk: the longest already-indexed prefix rides on
            # its existing pages; prefill starts at the first uncached
            # token.  A FULL (page-aligned) hit still has to compute
            # the last position's logits, so the divergence page is
            # copy-on-write: the shared page's KV is duplicated into a
            # private page and the one recomputed token writes there —
            # shared pages are never write targets.
            matched = self._cache.match(resume_ids)
            cow_src = None
            if matched and len(matched) * self.page_size >= resume:
                cow_src = matched.pop()
            prefill_off = (resume - 1 if cow_src is not None
                           else len(matched) * self.page_size)
            self._cache.retain(matched)   # pin before availability math
            n_alloc = need_total - len(matched)
            if n_alloc > self._cache.available():
                self._cache.release(matched)  # unpin: back to the LRU
                break                 # head-of-line: keep arrival order
            self._queue.popleft()
            alloc = []
            for _ in range(n_alloc):  # cannot dry up: available() holds
                alloc.append(self._cache.acquire(key=str(req.rid)))
            pages = matched + alloc
            s.req = req
            s.phase = "prefill"
            s.pages = pages
            s.prefill_ids = resume_ids
            s.prefill_off = prefill_off
            s.out_toks = list(req.done_toks)
            s.stop_len = stop
            s.eos = req.eos_token_id
            s.admit_seq = self._admit_counter
            self._admit_counter += 1
            self._bt[b, :] = 0
            self._bt[b, :len(pages)] = pages
            if cow_src is not None:
                self._cow_page(cow_src, alloc[0])
            self._stats["admitted"] += 1
            self._stats["pages_allocated"] += len(alloc)
            # demand is counted once per request: preempt resumes and
            # coordinator requeues re-admit the same logical request,
            # and re-counting them would report retry traffic as
            # prefill "savings" (computed stays net of cache restores)
            if not req.requested_counted:
                self._stats["prefill_tokens_requested"] += resume
                req.requested_counted = True
            self._tl.admitted(req.rid, b, cached_tokens=prefill_off,
                              resume_len=resume)
            if prefill_off:
                self._stats["cache_hits"] += 1
                self._stats["cache_hit_tokens"] += prefill_off
        self._note_peak()

    def _pick_victim(self, b):
        """Preemption victim for grower ``b``: the latest-admitted
        resident admitted AFTER ``b`` (never one ahead of it — the
        earliest resident must always win, which is what makes
        preemption converge). None when ``b`` is itself the latest."""
        me = self._slots[b].admit_seq
        victim, vseq = None, me
        for i, s in enumerate(self._slots):
            if i != b and s.req is not None and s.admit_seq > vseq:
                victim, vseq = i, s.admit_seq
        return victim

    def _preempt(self, b):
        """Evict slot ``b``: PUBLISH its fully-written pages into the
        prefix cache (they become ref-0 cached, not freed — LRU-newest,
        so they survive unless the pool is truly starved) and requeue
        the request at the HEAD (it outranks everything queued).
        Re-admission walks the index and restores from its own pages:
        only the tokens past the last full page re-prefill, closing
        the recompute gap of plain preempt-and-requeue."""
        s = self._slots[b]
        req = s.req
        req.done_toks = list(s.out_toks)
        req.preemptions += 1
        self._queue.appendleft(req)
        self._tl.preempted(req.rid, len(s.out_toks))
        self._publish_slot(b)
        self._release_slot(b)
        self._stats["preemptions"] += 1

    def _ensure_tokens(self, b, n_tokens):
        """Grow slot ``b``'s block table to hold ``n_tokens`` resident
        tokens.  Under pool pressure the allocator first EVICTS ref-0
        cached prefix pages (LRU), then preempts later-admitted victims
        (or under the injected ``engine_page_pressure`` drill, which
        forces the preempt path directly). Returns False when ``b``
        itself had to be preempted (it was the latest-admitted and the
        pool is exhausted)."""
        s = self._slots[b]
        need = -(-n_tokens // self.page_size)
        while len(s.pages) < need:
            pg = None
            if not faults.check(SITE_PAGE_PRESSURE, key=str(s.req.rid)):
                pg = self._cache.acquire(key=str(s.req.rid))
            if pg is None:
                victim = self._pick_victim(b)
                if victim is None:
                    self._preempt(b)
                    return False
                self._preempt(victim)
                continue
            self._bt[b, len(s.pages)] = pg
            s.pages.append(pg)
            self._stats["pages_allocated"] += 1
        self._note_peak()
        return True

    def step(self):
        """One scheduling step: retire, sweep policies, admit, grow/
        preempt, dispatch.  Returns the requests completed by the
        PREVIOUS dispatch plus any policy finalizations (retirement
        happens at step boundaries).  A page-accounting violation
        (``CacheIntegrityError``, PDT-E019 — an allocator bug, never a
        user error) dumps a flight record before propagating."""
        try:
            with _tracing.span("engine.step"):
                return self._step_inner()
        except CacheIntegrityError as e:
            _flight.dump("cache_integrity", error=e)
            raise

    def _step_inner(self):
        # the step's host time is split by spans on the profiler's
        # clock (observability/tracing.py): engine.retire, engine.sweep,
        # engine.admit here; engine.stage, serving.dispatch and
        # engine.readback in the _run_* that the step takes
        span = _tracing.span
        with span("engine.retire"):
            completed = self._retire()
            if self._early:
                completed.extend(self._early)
                self._early.clear()
        with span("engine.sweep"):
            now = self._clock()
            completed.extend(self._sweep(now))
            # SLO judgment rides the step boundary (throttled to the
            # evaluation interval — one float compare most steps, never
            # a per-token host sync)
            if self._slo is not None:
                self._slo.maybe_evaluate(now)
        with span("engine.admit"):
            self._admit()
        self._stats["steps"] += 1
        if self.spec_decode and any(
                s.phase in ("prefill", "decode") for s in self._slots):
            # speculative mode: ONE program serves prefill chunks AND
            # verify segments (q_lens up to spec_k+1) — the decode
            # window scan cannot host a Python-side proposer
            self._run_spec()
        elif any(s.phase == "prefill" for s in self._slots):
            self._run_mixed()
        elif any(s.phase == "decode" for s in self._slots):
            self._run_decode()
        elif self._queue:
            # backstop only: with every slot free the full pool is
            # available (cached prefix pages are all evictable once no
            # resident pins them) and eager PageBudgetError already
            # rejected anything that cannot fit it, so this is
            # unreachable for admissible request mixes
            req = self._queue[0]
            err = RuntimeError(
                f"request {req.rid} needs {self._admit_need(req)} pages "
                f"but the pool only has {self.total_pages - 1}; raise "
                "total_pages or lower max_new_tokens")
            _flight.dump("pool_backstop", error=err,
                         extra={"rid": req.rid})
            raise err
        return completed

    def _fail(self, b):
        """Decode guard hit: fail ONE request with the coded error; the
        engine and every co-resident request keep going.  The flight
        recorder dumps the recent event ring — the failed request's
        admission/prefill/decode timeline included — so the postmortem
        starts with context, not a bare error string."""
        s = self._slots[b]
        rid = s.req.rid
        err = DecodeGuard.failure(rid, s.len_written)
        self._stats["failed"] += 1
        self._early.append(self._finalize_slot(b, "failed", err))
        _flight.dump("nan_decode", error=err,
                     extra={"rid": rid, "slot": b})

    def _dispatch(self, kind, fn):
        def _on_retry(_exc, _attempt):
            self._stats["retries"] += 1
        # dispatch_retries counts RETRIES (re-attempts after a
        # transient), so N=0 disables retry and N=1 absorbs one fault.
        # Each dispatch runs under a serving.dispatch tracing span
        # (ISSUE 12): the span begin/end pair lands in the event ring
        # for export_trace, and the serving.dispatch timeline event
        # emitted INSIDE the span inherits its trace/parent ids — so a
        # trace carried in over rpc (disaggregated prefill/decode
        # handoff) threads through to the dispatch that served it.
        # With watchdog_ms > 0 the dispatch is also watchdog-armed
        # (ISSUE 14): past the deadline the stall thread's stacks and
        # the flight record are captured and EngineStallError is
        # injected here.  A truly stalled call never ran to
        # completion, so slot state is untouched and the next step()
        # re-plans the same dispatch bitwise.  A dispatch that
        # COMPLETES just past the deadline is the race case: its
        # donated buffers are already consumed, so discarding the
        # result would strand the engine — the completion cell below
        # records the result the instant fn() returns, a late
        # injection is swallowed and the real result used (the
        # residual few-bytecode window before the cell append can
        # still lose a result; the donated-buffer guards then fail
        # the NEXT dispatch loudly rather than corrupting state).
        timed = _obs_metrics.enabled()
        token = _watchdog.arm("serving.dispatch", self.watchdog_ms,
                              key=str(kind),
                              interrupt_exc=EngineStallError)
        done_cell = []

        def _fn_completing():
            out = fn()
            done_cell.append(out)
            return out

        try:
            try:
                with _tracing.span("serving.dispatch", op=str(kind)):
                    t0 = time.perf_counter() if timed else 0.0
                    res = dispatch_retry(
                        kind, _fn_completing,
                        max_attempts=self.dispatch_retries + 1,
                        on_retry=_on_retry)
                    token.disarm()   # close the injection window now —
                    # timeline/span bookkeeping must not be chargeable
                    if timed:
                        self._tl.dispatch(
                            kind, (time.perf_counter() - t0) * 1e3)
            except EngineStallError as e:
                if done_cell:
                    # late injection: the program ran; the result is
                    # real and its inputs are gone — keep it
                    res = done_cell[-1]
                else:
                    where = (f"; flight record at {token.dump_path}"
                             if token.dump_path else "")
                    raise EngineStallError(
                        f"engine dispatch {kind!r} stalled past the "
                        f"{self.watchdog_ms:g} ms watchdog deadline — "
                        f"thread stacks and the request timeline are "
                        f"in the flight record{where} "
                        f"[{EngineStallError.error_code}]") from e
        finally:
            token.disarm()
        return res

    # compiled serving programs cache ON the model (generate()'s
    # _decode_step_cache idiom): engines with the same bucket geometry
    # — page/table/pool shapes, token budget, slot count — share the
    # compiled mixed/decode programs instead of re-tracing
    def _program_cache(self):
        return self.model.__dict__.setdefault("_serving_step_cache", {})

    def _geometry(self):
        tp_key = None
        if self._tpp is not None:
            tp_key = (self.tp_axis,
                      tuple(d.id for d in self._jmesh.devices.flat))
        return (self.max_slots, self.page_size, self.np_per_seq,
                self.total_pages, self.token_budget, self.q_block,
                self.pages_per_block, self.kv_quant, tp_key)

    def _audit_program(self, name, fn, args, donated=()):
        """Whole-program audit (analysis/program.py) of a raw-jitted
        serving program: collective schedule, donation/HBM, recompile
        risk. Once per (program, geometry) — the audit runs at the
        dispatch that first compiles the program and never again, so
        steady-state dispatches do zero analysis work. The to_static
        programs (mixed/decode steps) are audited by the jit capture
        itself; this covers the ``jax.jit`` sites that bypass it."""
        from .. import analysis as _analysis
        if _analysis.mode() == "off":
            return
        done = self.model.__dict__.setdefault("_serving_audit_done",
                                              set())
        key = (name,) + self._geometry()
        if key in done:
            return
        done.add(key)
        _analysis.audit_jitted(fn, args, where=f"engine.{name}",
                               donated=donated)

    # ------------------------------------------- copy-on-write --------
    def _get_cow_fn(self):
        if self._cow_fn is None:
            key = ("cow", len(self._caches)) + self._geometry()
            cache = self._program_cache()
            self._cow_fn = cache.get(key)
            if self._cow_fn is None:
                n = len(self._caches)

                def cow(src, dst, *pools):
                    return tuple(p.at[:, dst].set(p[:, src])
                                 for p in pools)

                self._cow_fn = jax.jit(
                    cow, donate_argnums=tuple(range(2, 2 + n)))
                cache[key] = self._cow_fn
        return self._cow_fn

    def _cow_page(self, src, dst):
        """Copy-on-write at the divergence page: duplicate shared page
        ``src``'s KV (every layer pool) into private page ``dst`` in
        ONE donated-buffer dispatch — src/dst are traced scalars, so
        every COW event reuses the same compiled program.  The copied
        bits are exactly what this request's own prefill would have
        written, so the recompute that follows stays bitwise."""
        fn = self._get_cow_fn()
        vals = [c._read() for c in self._caches]
        self._audit_program(
            "cow", fn,
            (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
             *vals),
            donated=tuple(range(2, 2 + len(vals))))

        def _cow_call():
            # donated inputs: only retry while they are still alive
            # (same contract as the decode-window dispatch)
            if any(getattr(v, "is_deleted", lambda: False)()
                   for v in vals):
                raise RuntimeError(
                    "cow dispatch failed after its KV buffers were "
                    "donated; a mid-execution transient is "
                    "unrecoverable at this layer — re-create the "
                    "engine and re-submit the pending requests")
            return fn(jnp.asarray(src, jnp.int32),
                      jnp.asarray(dst, jnp.int32), *vals)

        new = self._dispatch("cow", _cow_call)
        for t, v in zip(self._caches, new):
            t._data = v
            t._node = None

    # ---------------------------------------------- TP adapters -------
    # the TP programs (models/generation.py make_tp_*) are plain jitted
    # shard_map functions over (data vectors, *sharded params, *cache
    # pools); these adapters give them the SAME call surface as the
    # to_static-compiled single-device programs — Tensors in, Tensors
    # out — so _run_mixed/_run_spec need no TP branch of their own
    def _tp_wrap(self, jitted, name="tp"):
        tpp = self._tpp
        n_caches = len(self._caches)

        def call(*args):
            vals = [a._read() for a in args]
            n_data = len(vals) - n_caches
            full = (*vals[:n_data], *tpp.vals, *vals[n_data:])
            self._audit_program(name, jitted, full)
            outs = jitted(*full)
            return tuple(Tensor(o) for o in outs)

        return call

    # ------------------------------------------------- mixed step -----
    def _get_mixed_fn(self):
        if self._mixed_fn is None:
            key = ("mixed", "guard") + self._geometry()
            cache = self._program_cache()
            self._mixed_fn = cache.get(key)
        if self._mixed_fn is None and self._tpp is not None:
            from ..models.generation import make_tp_mixed
            self._mixed_fn = self._tp_wrap(make_tp_mixed(
                self.model, self._tpp, self._jmesh, self.q_block,
                self.pages_per_block, len(self._caches)),
                name="tp_mixed")
            self._program_cache()[("mixed", "guard")
                                  + self._geometry()] = self._mixed_fn
        if self._mixed_fn is None:
            from .. import jit as jit_mod
            from .. import ops
            from ..models.generation import guarded_argmax
            model, ragged, qb = self.model, self._ragged, self.q_block
            ppb = self.pages_per_block

            def mixed(ids_t, tok_pos, tok_slot, tok_valid, kv_lens,
                      q_lens, last_idx, poison, bt, *cs):
                import paddle_tpu as pp
                with pp.no_grad():
                    logits, new = ragged(model, ids_t, tok_pos, tok_slot,
                                         tok_valid, kv_lens, q_lens, bt,
                                         list(cs), qb, ppb)
                    lg = ops.gather(logits, last_idx)       # [B, V]
                    nxt, bad = guarded_argmax(lg, poison)
                return (nxt, bad) + tuple(new)

            self._mixed_fn = jit_mod.to_static(mixed)
            cache[key] = self._mixed_fn
        return self._mixed_fn

    # shared segment planning/packing for the mixed AND speculative
    # dispatch paths — ONE implementation, so spec-on scheduling can
    # never drift from spec-off (the subsystem's bitwise-parity claim
    # rests on both paths planning prefill, growing pages and packing
    # tokens identically; only the decode-segment contents differ)
    def _plan_prefill(self, plan, budget):
        """Add each prefill slot's next chunk that fits ``budget`` to
        ``plan`` (entries ``(segment, pos0, take, drafts)``)."""
        qb = self.q_block
        for b, s in enumerate(self._slots):
            if s.phase != "prefill":
                continue
            rem = s.prefill_ids.size - s.prefill_off
            take = min(rem, budget)
            while take > 0 and -(-take // qb) * qb > budget:
                take -= 1     # q_block padding must fit the budget
            if take <= 0:
                continue      # budget exhausted: sits out this step
            budget -= -(-take // qb) * qb
            plan[b] = (list(s.prefill_ids[s.prefill_off:
                                          s.prefill_off + take]),
                       s.prefill_off, take, None)

    def _grow_plan(self, plan):
        """Page growth in admission order (earliest first — it can
        always win); growth may preempt later-admitted slots, planned
        or not, so drop plans whose slot got evicted or that
        self-preempted (latest + dry pool)."""
        order = sorted(plan, key=lambda b: self._slots[b].admit_seq)
        for b in order:
            s = self._slots[b]
            if s.req is None:           # evicted by an earlier grower
                plan.pop(b)
                continue
            seg, pos0, _take, _d = plan[b]
            if not self._ensure_tokens(b, pos0 + len(seg)):
                plan.pop(b)
        for b in list(plan):
            if self._slots[b].req is None:
                plan.pop(b)

    def _pack_plan(self, plan):
        """Pack the plan's segments into the token-budget vectors;
        returns ``(tok, tpos, tslot, tvalid, kv_lens, q_lens,
        last_idx, row0)`` with each segment starting at a q_block
        edge.  Also meters prefill compute (stats + timeline)."""
        qb, T, B = self.q_block, self.token_budget, self.max_slots
        tok = np.zeros(T, np.int32)
        tpos = np.zeros(T, np.int32)
        tslot = np.zeros(T, np.int32)
        tvalid = np.zeros(T, np.int32)
        kv_lens = np.ones(B, np.int32)
        q_lens = np.zeros(B, np.int32)
        last_idx = np.zeros(B, np.int32)
        row0 = {}
        cur = 0
        for b in range(B):
            if b not in plan:
                continue
            s = self._slots[b]
            seg, pos0, take, _d = plan[b]
            n = len(seg)
            tok[cur:cur + n] = seg
            tpos[cur:cur + n] = pos0 + np.arange(n)
            tslot[cur:cur + n] = b
            tvalid[cur:cur + n] = 1
            q_lens[b] = n
            kv_lens[b] = s.len_written + n
            last_idx[b] = cur + n - 1
            row0[b] = cur
            cur += -(-n // qb) * qb   # next segment at a q_block edge
            if take is not None:      # honest prefill-compute meter:
                self._stats["prefill_tokens_computed"] += take
                self._tl.prefill_chunk(s.req.rid, b, take, pos0)
        return (tok, tpos, tslot, tvalid, kv_lens, q_lens, last_idx,
                row0)

    def _run_mixed(self):
        """Pack one q_block-aligned segment per active slot — decode
        slots their current token, prefill slots the next chunk that
        fits — grow/preempt for the pages this step will write, and
        advance everything in ONE dispatch."""
        qb, T, B = self.q_block, self.token_budget, self.max_slots
        plan = {}      # b -> (segment, pos0, prefill take|None, drafts)
        budget = T
        for b, s in enumerate(self._slots):
            if s.phase == "decode":
                plan[b] = ([int(s.cur_tok)], s.cur_pos, None, None)
                budget -= qb
        self._plan_prefill(plan, budget)
        self._grow_plan(plan)
        if not plan:
            return
        with _tracing.span("engine.stage", op="mixed"):
            (tok, tpos, tslot, tvalid, kv_lens, q_lens, last_idx,
             _row0) = self._pack_plan(plan)
            poison = self._guard.poison(
                [self._slots[b].req.rid if b in plan else None
                 for b in range(B)])
            fn = self._get_mixed_fn()
            args = [Tensor(jnp.asarray(tok[None, :])),
                    Tensor(jnp.asarray(tpos)),
                    Tensor(jnp.asarray(tslot)),
                    Tensor(jnp.asarray(tvalid)),
                    Tensor(jnp.asarray(kv_lens)),
                    Tensor(jnp.asarray(q_lens)),
                    Tensor(jnp.asarray(last_idx)),
                    Tensor(jnp.asarray(poison)),
                    Tensor(jnp.asarray(self._bt))]
        res = self._dispatch("mixed", lambda: fn(*args, *self._caches))
        with _tracing.span("engine.readback", op="mixed"):
            nxt = np.asarray(res[0]._read()).reshape(-1)
            bad = np.asarray(res[1]._read()).reshape(-1)
        self._caches = list(res[2:])
        self._stats["mixed_steps"] += 1
        self._stats["decode_dispatches"] += 1
        for b in sorted(plan):
            s = self._slots[b]
            _seg, _pos0, take, _d = plan[b]
            if bad[b]:
                self._fail(b)
                continue
            if take is None:
                self._accept(s, int(nxt[b]))
            else:
                s.prefill_off += take
                if s.prefill_off >= s.prefill_ids.size:
                    s.phase = "decode"
                    s.cur_pos = s.prefill_ids.size
                    s.cur_tok = int(nxt[b])
                    s.out_toks.append(int(nxt[b]))
                    self._stats["tokens_generated"] += 1
                    self._tl.token(s.req.rid)

    def _accept(self, s, t):
        s.out_toks.append(t)
        s.cur_tok = t
        s.cur_pos += 1
        self._stats["tokens_generated"] += 1
        self._tl.token(s.req.rid)

    # --------------------------------------- speculative verify -------
    def _get_spec_fn(self):
        need_lg = self.spec_temperature > 0
        key = ("spec", "guard", need_lg) + self._geometry()
        cache = self._program_cache()
        if self._spec_fn is None:
            self._spec_fn = cache.get(key)
        if self._spec_fn is None and self._tpp is not None:
            from ..models.generation import make_tp_spec
            self._spec_fn = self._tp_wrap(make_tp_spec(
                self.model, self._tpp, self._jmesh, self.q_block,
                self.pages_per_block, len(self._caches), need_lg),
                name="tp_spec")
            cache[key] = self._spec_fn
        if self._spec_fn is None:
            from .. import jit as jit_mod
            from .. import ops
            from ..models.generation import verify_argmax
            model, ragged, qb = self.model, self._ragged, self.q_block
            ppb = self.pages_per_block

            if need_lg:
                # sampling mode returns per-slot logits ROWS gathered
                # in-graph ([B*(spec_k+1), V] — never the whole
                # [token_budget, V] block, whose prefill/padding rows
                # the host would not read)
                def spec(ids_t, tok_pos, tok_slot, tok_valid, kv_lens,
                         q_lens, poison, gather_idx, bt, *cs):
                    import paddle_tpu as pp
                    with pp.no_grad():
                        logits, new = ragged(
                            model, ids_t, tok_pos, tok_slot, tok_valid,
                            kv_lens, q_lens, bt, list(cs), qb, ppb)
                        toks, bad = verify_argmax(logits, tok_slot,
                                                  tok_valid, poison)
                        lgs = ops.gather(logits, gather_idx)
                    return (toks, bad, lgs) + tuple(new)
            else:
                def spec(ids_t, tok_pos, tok_slot, tok_valid, kv_lens,
                         q_lens, poison, bt, *cs):
                    import paddle_tpu as pp
                    with pp.no_grad():
                        logits, new = ragged(
                            model, ids_t, tok_pos, tok_slot, tok_valid,
                            kv_lens, q_lens, bt, list(cs), qb, ppb)
                        toks, bad = verify_argmax(logits, tok_slot,
                                                  tok_valid, poison)
                    return (toks, bad) + tuple(new)

            self._spec_fn = jit_mod.to_static(spec)
            cache[key] = self._spec_fn
        return self._spec_fn

    def _run_spec(self):
        """Speculative mixed step (ISSUE 9): prefill slots pack chunks
        exactly like :meth:`_run_mixed`; decode slots pack their
        current token plus up to ``spec_k`` proposed tokens as a
        ragged VERIFY segment (``q_lens = K+1`` — per-sequence lengths
        are DATA to the kernel, so this is the same compiled program
        every step) and advance by the accepted length.  Retirement is
        RAGGED: each slot's ``cur_pos``/``len_written`` moves by its
        own accept count, and KV written past the first rejection is
        rolled back positionally — ``kv_lens`` masks it and the next
        dispatch overwrites the same (page, slot) bytes, so published
        prefix pages only ever hold accepted tokens."""
        qb, T, B = self.q_block, self.token_budget, self.max_slots
        plan = {}   # b -> (segment, pos0, prefill take|None, drafts)
        budget = T
        for b, s in enumerate(self._slots):
            if s.phase != "decode":
                continue
            # room: at most stop_len - cur_pos - 1 tokens may still be
            # emitted and one verify emits up to K+1, so K is clamped
            # to keep every written position inside the page table
            k = min(self.spec_k, max(s.stop_len - s.cur_pos - 2, 0))
            drafts = np.empty(0, np.int32)
            if k > 0:
                ids = np.concatenate(
                    [s.req.prompt, np.asarray(s.out_toks, np.int32)])
                drafts = np.asarray(
                    self._proposer.propose(s.req.rid, ids, k),
                    np.int32).reshape(-1)[:k]
                if drafts.size and faults.check(
                        SITE_DRAFT_MISMATCH, key=str(s.req.rid)):
                    # drill: corrupt the proposal so this verify step
                    # rejects it — outputs must stay bitwise, only the
                    # accept rate moves
                    drafts = ((drafts + 1)
                              % self.model.cfg.vocab_size).astype(
                                  np.int32)
            seg = [int(s.cur_tok)] + [int(t) for t in drafts]
            plan[b] = (seg, s.cur_pos, None, drafts)
            budget -= -(-len(seg) // qb) * qb
        self._plan_prefill(plan, budget)
        self._grow_plan(plan)
        if not plan:
            return
        with _tracing.span("engine.stage", op="verify"):
            (tok, tpos, tslot, tvalid, kv_lens, q_lens, _last_idx,
             row0) = self._pack_plan(plan)
            # the standing nan drill arms on every dispatch a slot rides;
            # engine_draft_nan arms ONLY on slots with a verify segment
            # this dispatch (the site's documented scope)
            poison = self._guard.poison(
                [self._slots[b].req.rid if b in plan else None
                 for b in range(B)])
            poison = poison + self._guard.poison(
                [self._slots[b].req.rid
                 if b in plan and plan[b][2] is None else None
                 for b in range(B)], sites=(SITE_DRAFT_NAN,))
            need_lg = self.spec_temperature > 0
            W = self.spec_k + 1            # gathered rows per slot
            fn = self._get_spec_fn()
            args = [Tensor(jnp.asarray(tok[None, :])),
                    Tensor(jnp.asarray(tpos)), Tensor(jnp.asarray(tslot)),
                    Tensor(jnp.asarray(tvalid)),
                    Tensor(jnp.asarray(kv_lens)),
                    Tensor(jnp.asarray(q_lens)),
                    Tensor(jnp.asarray(poison))]
            if need_lg:
                # sampling needs logits rows: slot b's W-row window holds
                # its verify rows (padded by repetition) — or, for a
                # prefill slot, its LAST chunk row at window position 0
                # (the first-token sample when the chunk completes prefill)
                gather_idx = np.zeros(B * W, np.int32)
                for b, (seg, _pos0, take, _d) in plan.items():
                    if take is None:
                        n = len(seg)
                        idx = row0[b] + np.minimum(np.arange(W), n - 1)
                    else:
                        idx = np.full(W, row0[b] + take - 1)
                    gather_idx[b * W:(b + 1) * W] = idx
                args.append(Tensor(jnp.asarray(gather_idx)))
            args.append(Tensor(jnp.asarray(self._bt)))
        res = self._dispatch("verify",
                             lambda: fn(*args, *self._caches))
        with _tracing.span("engine.readback", op="verify"):
            toks = np.asarray(res[0]._read()).reshape(-1)
            bad = np.asarray(res[1]._read()).reshape(-1)
            n_head = 2
            logits = None
            if need_lg:
                logits = np.asarray(res[2]._read()).astype(
                    np.float32).reshape(B * W, -1)
                n_head = 3
        self._caches = list(res[n_head:])
        self._stats["decode_dispatches"] += 1
        if any(p[2] is not None for p in plan.values()):
            self._stats["mixed_steps"] += 1
        for b in sorted(plan):
            s = self._slots[b]
            seg, pos0, take, drafts = plan[b]
            if bad[b]:
                self._fail(b)        # per-draft guard: this slot alone
                continue
            if take is not None:     # prefill chunk — as _run_mixed,
                s.prefill_off += take       # except a sampling engine
                if s.prefill_off >= s.prefill_ids.size:  # SAMPLES the
                    if need_lg:                     # first token too
                        nxt = self._sample_row(logits[b * W])
                    else:
                        nxt = int(toks[row0[b] + take - 1])
                    s.phase = "decode"
                    s.cur_pos = s.prefill_ids.size
                    s.cur_tok = nxt
                    s.out_toks.append(nxt)
                    self._stats["tokens_generated"] += 1
                    self._tl.token(s.req.rid)
                continue
            # verify: greedy accepts the longest agreed draft prefix
            # plus the target's free next token; spec_temperature > 0
            # switches to the sampling rule over the gathered logits
            n = len(seg)
            if need_lg:
                emitted, m = _spec.accept_sampled(
                    drafts, logits[b * W:b * W + n],
                    self.spec_temperature, self._spec_rng,
                    rejection_sampling=self.spec_rejection_sampling)
            else:
                emitted, m = _spec.accept_greedy(
                    drafts, toks[row0[b]:row0[b] + n])
            self._spec_stats["spec_proposed"] += int(drafts.size)
            self._spec_stats["spec_accepted"] += int(m)
            adv = 0
            for t in emitted:
                self._accept(s, int(t))
                adv += 1
                if (s.eos >= 0 and int(t) == s.eos) \
                        or s.cur_pos + 1 >= s.stop_len:
                    break            # host replay of the stop rule
            self._tl.verify_window(s.req.rid, int(drafts.size),
                                   int(m), adv)

    def _sample_row(self, row):
        """Sample one token from a single logits row at the engine's
        speculative temperature (the prefill-completion token of a
        sampling-mode engine — argmax here would leak a greedy token
        into an otherwise exactly-sampled stream).  Routes through
        ``accept_sampled``'s free-token path so the sampling rule has
        ONE home and cannot drift."""
        emitted, _ = _spec.accept_sampled(
            np.empty(0, np.int32), row[None], self.spec_temperature,
            self._spec_rng)
        return int(emitted[0])

    # ------------------------------------------------ decode window ---
    def _get_step_fn(self):
        if self._step_fn is None:
            key = ("decode",) + self._geometry()
            cache = self._program_cache()
            self._step_fn = cache.get(key)
        if self._step_fn is None:
            from .. import jit as jit_mod
            from ..models.generation import paged_slot_attention
            model, decode = self.model, self._decode
            ppb = self.pages_per_block

            def step(tok, pos, bt, *cs):
                import paddle_tpu as pp
                with pp.no_grad():
                    def attend(q, k, v, kc, vc, p, ks=None, vs=None):
                        return paged_slot_attention(
                            q, k, v, kc, vc, p, bt,
                            pages_per_block=ppb, k_scales=ks,
                            v_scales=vs)
                    logits, new = decode(model, tok, pos, list(cs),
                                         attend=attend)
                return (logits,) + tuple(new)

            self._step_fn = jit_mod.to_static(step)
            self._program_cache()[key] = self._step_fn
        return self._step_fn

    def _slot_vectors(self):
        B = self.max_slots
        tok = np.zeros((B, 1), np.int32)
        pos = np.zeros(B, np.int32)
        fin = np.ones(B, bool)
        eos = np.full(B, -1, np.int32)
        stop = np.ones(B, np.int32)
        rids = [None] * B
        for b, s in enumerate(self._slots):
            if s.phase != "decode":
                continue
            tok[b, 0] = s.cur_tok
            pos[b] = s.cur_pos
            fin[b] = s.done
            eos[b] = s.eos
            stop[b] = s.stop_len
            rids[b] = s.req.rid
        return tok, pos, fin, eos, stop, rids

    def _grow_decode_slots(self):
        """Reserve the pages the next decode dispatch can write: up to
        ``decode_window`` tokens per live slot (capped at stop_len),
        preempting under pressure. Earliest-admitted first."""
        order = sorted(
            (b for b, s in enumerate(self._slots)
             if s.phase == "decode"),
            key=lambda b: self._slots[b].admit_seq)
        for b in order:
            s = self._slots[b]
            if s.req is None:           # evicted by an earlier grower
                continue
            target = min(s.cur_pos + self.decode_window, s.stop_len)
            self._ensure_tokens(b, max(target, s.cur_pos + 1))

    def _run_decode(self):
        self._grow_decode_slots()
        if not any(s.phase == "decode" for s in self._slots):
            return                      # everyone got preempted
        with _tracing.span("engine.stage", op="decode"):
            tok, pos, fin, eos, stop, rids = self._slot_vectors()
        if self._tpp is not None:
            # TP path: the scanned window program is self-contained
            # (explicit sharded params, no captured executable state),
            # so there is no first-scalar-dispatch bootstrap — every
            # decode dispatch is a window.  Token streams are identical
            # either way: the host replay of the stop rule is shared.
            self._run_tp_window(tok, pos, fin, eos, stop, rids)
            return
        step_fn = self._get_step_fn()
        if self._decode_exe is None:
            # a model-cache hit may hand us an already-compiled step
            wrapped = (step_fn if hasattr(step_fn, "_cache")
                       else getattr(step_fn, "__wrapped__", None))
            if wrapped is not None and getattr(wrapped, "_cache", None):
                self._decode_exe = next(iter(wrapped._cache.values()))
        if self._decode_exe is None:
            # first decode dispatch compiles the scalar step; its logits
            # advance every live slot by one token (host argmax; the
            # guard check runs host-side on the same poisoned values
            # the windowed path applies in-graph)
            res = self._dispatch("decode", lambda: step_fn(
                Tensor(jnp.asarray(tok)), Tensor(jnp.asarray(pos)),
                Tensor(jnp.asarray(self._bt)), *self._caches))
            with _tracing.span("engine.readback", op="decode"):
                lg = np.asarray(res[0]._read()).astype(np.float32)
            self._caches = list(res[1:])
            lg = lg + self._guard.poison(rids)[:, None]
            bad = ~np.isfinite(lg).all(-1)
            nxt = np.where(bad, 0, lg.argmax(-1)).astype(np.int32)
            self._stats["decode_dispatches"] += 1
            accepted = 0
            for b, s in enumerate(self._slots):
                if fin[b]:
                    continue
                if bad[b]:
                    self._fail(b)
                    continue
                self._accept(s, int(nxt[b]))
                accepted += 1
            self._tl.decode_window(accepted, int((~fin).sum()))
            wrapped = (step_fn if hasattr(step_fn, "_cache")
                       else getattr(step_fn, "__wrapped__", None))
            if wrapped is not None and getattr(wrapped, "_cache", None):
                self._decode_exe = next(iter(wrapped._cache.values()))
            return
        self._run_window(tok, pos, fin, eos, stop, rids)

    def _get_window_runner(self, K):
        # cached on the executable (generate()'s idiom) so engines
        # sharing a compiled step also share its window programs
        runners = self._decode_exe.__dict__.setdefault(
            "_slot_window_cache", {})
        runner = runners.get(K)
        if runner is None:
            runner = _make_slot_window(self._decode_exe, K)
            runners[K] = runner
        return runner

    def _run_window(self, tok, pos, fin, eos, stop, rids):
        """K scanned decode steps in one dispatch; slot state rides the
        scan carry (models/generation.py's window machinery, per-slot).
        The guard's bad flag is part of the carry: a slot that goes
        non-finite freezes in-graph and is failed host-side."""
        exe = self._decode_exe
        K = self.decode_window
        with _tracing.span("engine.stage", op="window"):
            for sync in exe.discovery.host_syncs:
                sync()
            capt = exe.capt_state
            carry_idx, const_idx = exe.state_split()
            cache_vals = [c._read() for c in self._caches]
            cstate = [capt[i]._read() for i in carry_idx]
            const_state = [capt[i]._read() for i in const_idx]
            poison = self._guard.poison(rids)
            runner = self._get_window_runner(K)
            self._audit_program(
                ("window", K), runner,
                (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(fin),
                 jnp.asarray(np.zeros(self.max_slots, bool)),
                 jnp.asarray(eos), jnp.asarray(stop), jnp.asarray(poison),
                 jnp.asarray(self._bt), cache_vals, cstate, const_state))
        donated = cache_vals + cstate    # runner donate_argnums=(8, 9)

        def _window_call():
            # retry can only re-run this closure while its donated
            # inputs are still alive (a transient raised BEFORE the
            # program consumed them — the engine_dispatch drill, a
            # submit-side connection error). Past donation the buffers
            # are gone: surface that clearly instead of retrying into
            # a confusing deleted-buffer error.
            if any(getattr(v, "is_deleted", lambda: False)()
                   for v in donated):
                raise RuntimeError(
                    "decode-window dispatch failed after its KV/state "
                    "buffers were donated; a mid-execution transient "
                    "is unrecoverable at this layer — re-create the "
                    "engine and re-submit the pending requests")
            return runner(
                jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(fin),
                jnp.asarray(np.zeros(self.max_slots, bool)),
                jnp.asarray(eos), jnp.asarray(stop),
                jnp.asarray(poison), jnp.asarray(self._bt),
                cache_vals, cstate, const_state)

        toks, bads, tokf, posf, finf, badf, cache_vals, cstate = \
            self._dispatch("window", _window_call)
        for i, v in zip(carry_idx, cstate):
            capt[i]._data = v
            capt[i]._node = None
        for t, v in zip(self._caches, cache_vals):
            t._data = v
            t._node = None
        self._stats["decode_dispatches"] += 1
        with _tracing.span("engine.readback", op="window"):
            toks, bads = np.asarray(toks), np.asarray(bads)
        self._apply_window(toks, bads, fin, K)

    def _apply_window(self, toks, bads, fin, K):
        """Host replay of the device stop rule over one decode
        window's stacked tokens [K, B] / cumulative bad flags [K, B]
        (identical predicate, so the accepted prefix matches the
        carried fin exactly); the first bad step fails the slot and
        discards its frozen tail.  Shared by the single-device and TP
        window paths — the bitwise claim between them rests on this
        being ONE implementation."""
        live = accepted = 0
        for b, s in enumerate(self._slots):
            if s.phase != "decode" or fin[b]:
                continue
            live += 1
            for k in range(K):
                if bads[k, b]:
                    self._fail(b)
                    break
                t = int(toks[k, b])
                self._accept(s, t)
                accepted += 1
                if (s.eos >= 0 and t == s.eos) \
                        or s.cur_pos + 1 >= s.stop_len:
                    break
        self._tl.decode_window(accepted, live)

    def _get_tp_window(self, K):
        key = ("tpwin", K) + self._geometry()
        cache = self._program_cache()
        runner = cache.get(key)
        if runner is None:
            from ..models.generation import make_tp_window
            runner = make_tp_window(self.model, self._tpp, self._jmesh,
                                    self.pages_per_block,
                                    len(self._caches), K)
            cache[key] = runner
        return runner

    def _run_tp_window(self, tok, pos, fin, eos, stop, rids):
        """K scanned TP decode steps in one dispatch — the sharded
        analog of :meth:`_run_window` (same carry discipline, same
        donated-cache retry contract, same host replay)."""
        K = self.decode_window
        runner = self._get_tp_window(K)
        with _tracing.span("engine.stage", op="window"):
            cache_vals = [c._read() for c in self._caches]
            poison = self._guard.poison(rids)
            self._audit_program(
                ("tpwin", K), runner,
                (jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(fin),
                 jnp.asarray(np.zeros(self.max_slots, bool)),
                 jnp.asarray(eos), jnp.asarray(stop),
                 jnp.asarray(poison), jnp.asarray(self._bt),
                 *self._tpp.vals, *cache_vals))

        def _window_call():
            if any(getattr(v, "is_deleted", lambda: False)()
                   for v in cache_vals):
                raise RuntimeError(
                    "decode-window dispatch failed after its KV "
                    "buffers were donated; a mid-execution transient "
                    "is unrecoverable at this layer — re-create the "
                    "engine and re-submit the pending requests")
            return runner(
                jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(fin),
                jnp.asarray(np.zeros(self.max_slots, bool)),
                jnp.asarray(eos), jnp.asarray(stop),
                jnp.asarray(poison), jnp.asarray(self._bt),
                *self._tpp.vals, *cache_vals)

        res = self._dispatch("window", _window_call)
        toks, bads = res[0], res[1]
        for t, v in zip(self._caches, res[6:]):
            t._data = v
            t._node = None
        self._stats["decode_dispatches"] += 1
        with _tracing.span("engine.readback", op="window"):
            toks, bads = np.asarray(toks), np.asarray(bads)
        self._apply_window(toks, bads, fin, K)


def _make_slot_window(exe, K):
    """Scan K per-slot greedy decode steps into ONE jitted dispatch.
    The carry holds (token, position, finished, guard-bad) PER SLOT
    plus caches and mutated captured state; finished OR guard-failed
    slots freeze (position and token stop advancing, so their page
    writes keep landing on already owned — or null — pages). The
    stacked per-step bad flags come back so the host can locate the
    first poisoned step exactly."""
    from jax import lax

    from ..models.generation import guarded_argmax

    pure = exe._pure
    n_ret = exe.n_ret
    n_caches = n_ret - 1
    capt = exe.capt_state
    carry_idx, const_idx = exe.state_split()

    def window(tok, pos, fin, bad, eos_ids, stop_lens, poison, bt,
               caches, cstate, const_state):
        def body(c, _):
            tok, pos, fin, bad, caches, cstate = c
            state = [None] * len(capt)
            for i, v in zip(carry_idx, cstate):
                state[i] = v
            for i, v in zip(const_idx, const_state):
                state[i] = v
            outs = pure(tok, pos, bt, *caches, *state)
            lg = outs[0].astype(jnp.float32)
            new_caches = list(outs[1:1 + n_caches])
            new_cstate = list(outs[1 + n_caches:
                                   1 + n_caches + len(carry_idx)])
            nxt_raw, row_bad = guarded_argmax.raw(lg, poison)     # [B]
            bad2 = bad | (row_bad & jnp.logical_not(fin))
            adv = jnp.logical_not(fin | bad2)
            nxt = jnp.where(adv, nxt_raw, tok[:, 0])
            pos2 = jnp.where(adv, pos + 1, pos)
            fin2 = fin | bad2 | ((eos_ids >= 0) & (nxt == eos_ids)) \
                | (pos2 + 1 >= stop_lens)
            return (nxt[:, None], pos2, fin2, bad2, new_caches,
                    new_cstate), (nxt, bad2)

        (tok, pos, fin, bad, caches, cstate), (toks, bads) = lax.scan(
            body, (tok, pos, fin, bad, caches, cstate), None, length=K)
        return toks, bads, tok, pos, fin, bad, caches, cstate

    return jax.jit(window, donate_argnums=(8, 9))
