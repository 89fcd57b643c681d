"""Global framework state: default dtype, grad mode, RNG, flags.

Capability analog of the reference flags/env system (SURVEY C1,
``paddle/common/flags.cc``) and the global tracer state
(``paddle/fluid/imperative/tracer.h``).
"""
from __future__ import annotations

import os
import threading

import numpy as np

DEFAULT_DTYPE = np.dtype("float32")

# --- flags registry (analog of PHI_DEFINE_EXPORTED_*; env override via
# PDTPU_<name>, mirroring FLAGS_<name> env behavior in flags_native.cc) ---
_FLAGS: dict[str, object] = {}
_FLAG_DEFS: dict[str, tuple[type, object, str]] = {}


def define_flag(name: str, default, help_str: str = ""):
    ftype = type(default)
    env = os.environ.get("PDTPU_" + name.upper())
    val = default
    if env is not None:
        if ftype is bool:
            val = env.lower() in ("1", "true", "yes")
        else:
            val = ftype(env)
    _FLAG_DEFS[name] = (ftype, default, help_str)
    _FLAGS[name] = val
    return val


def get_flags(names=None):
    if names is None:
        return dict(_FLAGS)
    if isinstance(names, str):
        names = [names]
    return {n: _FLAGS[n] for n in names}


def set_flags(flags: dict):
    for k, v in flags.items():
        if k not in _FLAG_DEFS:
            raise KeyError(f"unknown flag {k!r}")
        _FLAGS[k] = _FLAG_DEFS[k][0](v)


def get_flag(name: str):
    return _FLAGS[name]


# Core flags (subset of the 138 reference flags that are meaningful on TPU).
define_flag("check_nan_inf", False, "scan op outputs for nan/inf (numeric sanitizer)")
define_flag("check_nan_inf_level", 0, "0: error on nan/inf; 3: only log stats")
define_flag("benchmark", False, "sync + time every op")
define_flag("eager_delete_tensor_gb", 0.0, "GC threshold (no-op under XLA; kept for parity)")
define_flag("use_stride_kernel", True, "allow view/stride ops to alias (jax always copies-on-write)")
define_flag("log_level", 0, "framework VLOG level")
define_flag("analysis", "warn",
            "graph-lint mode (paddle_tpu.analysis): off = analyzers "
            "skipped entirely; warn = findings surface as LintWarnings "
            "(notes to the logger); error = any warn-or-worse finding "
            "raises StaticAnalysisError. Env override PDTPU_ANALYSIS.")
define_flag("fused_opt", True,
            "flat-buffer multi-tensor optimizer path (optimizer/flat.py "
            "+ ops/pallas/fused_optimizer.py): dtype-bucketed flat "
            "params/grads/moments updated by one fused kernel per "
            "bucket. PDTPU_FUSED_OPT=off force-disables (per-param "
            "fallback). Exotic cases (per-param LR/clip/regularizer, "
            "sharded or lazy params, unsupported optimizers/clips) fall "
            "back automatically.")
define_flag("serving_max_queue", 0,
            "bounded admission queue for inference.ContinuousBatching"
            "Engine: add_request past this depth applies the queue "
            "policy. 0 = unbounded (lab default; PDT109 notes it). "
            "Engine kwarg max_queue overrides per instance.")
define_flag("serving_queue_policy", "reject",
            "what a full serving queue does to add_request: 'reject' "
            "raises QueueFullError (PDT-E017) so the caller sheds "
            "load; 'block' steps the engine until room frees. Engine "
            "kwarg queue_policy overrides per instance.")
define_flag("serving_deadline_ms", 0.0,
            "default per-request deadline for the serving engine, "
            "checked at step boundaries (finish_reason 'timeout'). "
            "0 = no deadline. add_request(deadline_ms=...) overrides "
            "per request.")
define_flag("serving_dispatch_retries", 3,
            "bounded resilience.retry RE-attempts after a transient "
            "failure of a serving engine dispatch (N retries = N+1 "
            "attempts; 0 disables retry). Transient ConnectionErrors "
            "— incl. the injected engine_dispatch fault site — are "
            "absorbed; anything else propagates.")
define_flag("serving_prefix_cache", True,
            "cross-request KV prefix cache for the serving engine "
            "(inference/prefix_cache.py): admissions map shared "
            "prompt/few-shot prefixes onto already-written KV pages "
            "via a radix index (copy-on-write at the divergence page, "
            "LRU eviction under pool pressure) and preempt-requeue "
            "re-admission restores from its own published pages "
            "instead of re-prefilling. Outputs are bitwise-identical "
            "either way. PDTPU_SERVING_PREFIX_CACHE=off restores "
            "uncached admission; engine kwarg prefix_cache overrides "
            "per instance. PDT110 notes high-traffic engines built "
            "with the cache off.")
# String spellings that disable the prefix cache, shared by the engine's
# prefix_cache kwarg parse and the PDT110 lint so they cannot diverge.
PREFIX_CACHE_OFF_SPELLINGS = ("off", "false", "0", "no")
define_flag("serving_kv_quant", False,
            "int8 KV page pools for the serving engine (ISSUE 7): "
            "pages store int8 with per-page scale side-pools "
            "(quantization.kv_quantize), dequantized inside the ragged "
            "paged-attention kernel's DMA loop — KV bytes per resident "
            "sequence drop >2x at token-identical greedy "
            "outputs on the serving parity suite. Default off; "
            "PDTPU_SERVING_KV_QUANT=1 (or engine kwarg kv_quant) "
            "enables, and the off state is bitwise-identical to the "
            "pre-quantization fp path.")
# Spellings that toggle KV quantization in the engine's kv_quant kwarg
# (off set shared with the prefix cache — one convention for on/off
# strings).  Unlike prefix_cache (bitwise-identical either way), this
# switch changes numerics, so unrecognized spellings must never
# silently enable it: the engine raises, the env alias ignores.
KV_QUANT_OFF_SPELLINGS = PREFIX_CACHE_OFF_SPELLINGS
KV_QUANT_ON_SPELLINGS = ("on", "true", "1", "yes")
# Both env spellings — the canonical PDTPU_SERVING_KV_QUANT the flag
# registry derives and the short PDTPU_KV_QUANT alias — parse through
# the SAME on/off sets (define_flag's bool parse misses "on"), the
# alias taking precedence when both are set.
for _env_name in ("PDTPU_SERVING_KV_QUANT", "PDTPU_KV_QUANT"):
    _env_kvq = os.environ.get(_env_name)
    if _env_kvq is not None:
        if _env_kvq.lower() in KV_QUANT_ON_SPELLINGS:
            _FLAGS["serving_kv_quant"] = True
        elif _env_kvq.lower() in KV_QUANT_OFF_SPELLINGS:
            _FLAGS["serving_kv_quant"] = False
del _env_name, _env_kvq
define_flag("serving_spec_decode", False,
            "speculative decoding for the serving engine (ISSUE 9, "
            "inference/speculative.py): per decode step each slot "
            "submits its current token plus K proposed tokens as one "
            "ragged verify segment (q_lens=K+1 through the existing "
            "mixed program) and advances by the longest draft prefix "
            "the target model agrees with plus one free token. Greedy "
            "outputs are bitwise-identical to the flag off; only "
            "tokens-per-dispatch moves. Engine kwarg spec_decode "
            "overrides per instance.")
define_flag("serving_spec_k", 4,
            "draft tokens proposed per slot per speculative decode "
            "step (the verify segment is K+1 rows, padded to the "
            "engine's q_block). Engine kwarg spec_k overrides.")
define_flag("serving_spec_proposer", "ngram",
            "default proposer for spec_decode engines: 'ngram' is the "
            "model-free prompt-lookup proposer (zero extra FLOPs). "
            "Pass a Proposer instance (e.g. DraftModelProposer) via "
            "the engine's spec_proposer kwarg for a draft model.")
define_flag("serving_spec_temperature", 0.0,
            "speculative-mode sampling temperature. 0 (default) = "
            "greedy token-equality acceptance, bitwise vs plain "
            "decode. > 0 samples the target's tokens — pair it with "
            "serving_spec_rejection_sampling or the output "
            "distribution skews toward the proposer (PDT113).")
define_flag("serving_spec_rejection_sampling", False,
            "lossless speculative SAMPLING acceptance: drafts accept "
            "with probability p(draft) under the temperature-scaled "
            "target distribution and rejections resample from the "
            "residual, so the output distribution is exactly the "
            "target's. Only meaningful with "
            "serving_spec_temperature > 0.")
define_flag("serving_tp", 0,
            "default tensor-parallel degree for serving engines "
            "(ISSUE 13): a ContinuousBatchingEngine constructed "
            "WITHOUT mesh= builds a 1-axis mesh over the first N "
            "devices and shards its two compiled programs over it — "
            "weights column/row split per the canonical Megatron "
            "rules, KV page pools sharded by kv-head (GQA-aware), "
            "block tables/lengths replicated, one psum at the "
            "attention output and the MLP reduce. 0/1 = single-device "
            "(today's engine, bitwise). Engine kwargs mesh=/tp_axis= "
            "override per instance; greedy outputs are token-identical "
            "to the single-device engine either way. PDT116 notes "
            "engines built single-device while a multi-device mesh is "
            "in scope.")
define_flag("serving_disagg_prefill_workers", 1,
            "default prefill-group size for inference.DisaggServer "
            "(disaggregated prefill/decode serving): how many engine "
            "instances admit + chunk-prefill new requests before the "
            "KV-page handoff. DisaggServer kwarg prefill_workers "
            "overrides.")
define_flag("serving_disagg_decode_workers", 1,
            "default decode-group size for inference.DisaggServer: "
            "how many engine instances run the latency-bound decode "
            "windows on handed-off KV pages. DisaggServer kwarg "
            "decode_workers overrides.")
define_flag("serving_disagg_handoff_retries", 3,
            "bounded resilience.retry RE-attempts for one KV-page "
            "handoff transfer (KVPageTransport.ship) after a "
            "transient ConnectionError — incl. the injected "
            "engine_handoff_transient fault site. N retries = N+1 "
            "attempts; 0 disables retry.")
define_flag("serving_fleet_replicas", 2,
            "default live-replica count for inference.FleetRouter "
            "when replicas= is an int or omitted: how many "
            "ContinuousBatchingEngine workers the router builds over "
            "the shared model (compiled serving programs cache on the "
            "model, so N same-geometry replicas compile once). "
            "FleetRouter kwarg replicas overrides.")
define_flag("serving_fleet_affinity", True,
            "prefix-cache-aware placement for inference.FleetRouter: "
            "route each prompt to the replica whose radix prefix "
            "cache reports the longest page-aligned hit "
            "(cached_prefix_tokens), spilling to the least-loaded "
            "replica when no replica holds the prefix. False = "
            "deterministic round-robin over the live replicas. "
            "FleetRouter kwarg affinity overrides.")
define_flag("serving_fleet_heartbeat_ms", 0.0,
            "fleet-router replica heartbeat timeout (ms): a live "
            "replica whose last successful step is older than this is "
            "declared dead (generation bump, coded flight record, "
            "queued + in-flight requests requeued to survivors). 0 "
            "disables the timeout detector — in-process replicas beat "
            "synchronously, so the timeout matters for rpc-backed "
            "replicas. FleetRouter kwarg heartbeat_timeout_ms "
            "overrides.")
define_flag("serving_fleet_dispatch_retries", 3,
            "bounded resilience.retry RE-attempts for one fleet-"
            "router placement dispatch (replica add_request) after a "
            "transient ConnectionError — incl. the injected "
            "router_dispatch_transient fault site. Exhausting the "
            "budget declares the replica dead and requeues the "
            "request. N retries = N+1 attempts; 0 disables retry. "
            "FleetRouter kwarg dispatch_retries overrides.")
define_flag("serving_fleet_scaleout_timeout_ms", 0.0,
            "watchdog deadline (ms) for admitting a standby replica "
            "on a sustained fleet-SLO burn-rate breach: past it the "
            "admission surfaces EngineStallError (PDT-E020) with a "
            "flight record and the fleet degrades gracefully on the "
            "live replicas. 0 disarms the watchdog (the "
            "router_scaleout_stall drill then raises after its "
            "bounded spin). FleetRouter kwarg scaleout_timeout_ms "
            "overrides.")
define_flag("serving_fleet_scalein_hold_s", 30.0,
            "how long the fleet SLO must stay recovered (no breached "
            "spec) before the fleet router drains a scaled-out "
            "standby back: the replica stops taking placements and "
            "returns to standby once idle. FleetRouter kwarg "
            "scalein_hold_s overrides.")
define_flag("serving_fleet_slo", "",
            "fleet-wide objectives for the serving router "
            "(inference/router.py): same spec grammar as serving_slo "
            "('queue_p95_ms=200,goodput=0.99'), evaluated over the "
            "ROUTER's registry (admission-queue wait, fleet finish "
            "reasons) rather than any one replica's. A sustained "
            "burn-rate breach admits a standby replica (scale-out); "
            "holding recovered for serving_fleet_scalein_hold_s "
            "drains it back. '' (default) arms nothing — no "
            "SLO-driven scaling; FleetRouter kwarg fleet_slo "
            "overrides.")
define_flag("serving_migration", False,
            "live request migration for the serving fleet (ISSUE 20): "
            "FleetRouter drain/scale-in/lame-duck MIGRATES resident "
            "requests warm to surviving replicas over the PR13 "
            "KVPageTransport (engine snapshot_request/restore_request) "
            "instead of waiting for in-flight decode or cold-requeuing "
            "prefilled work. Bitwise: a migrated stream equals the "
            "unmigrated stream token-for-token (greedy decode is "
            "deterministic and KV bytes are a pure function of the "
            "token prefix). Off (default) = PR17 behavior — drain "
            "waits, death cold-requeues; PDT122 notes routers that "
            "drain cold while deadlines/SLOs are configured. "
            "FleetRouter kwarg migration overrides.")
define_flag("serving_lameduck_ms", 0.0,
            "degraded-heartbeat age (ms) past which a live fleet "
            "replica enters LAME-DUCK: new placements stop and its "
            "residents are proactively migrated to survivors BEFORE "
            "the serving_fleet_heartbeat_ms death deadline, so a "
            "planned preemption (maintenance event, preemptible "
            "capacity) loses zero prefill work. Must be smaller than "
            "the heartbeat timeout to matter; 0 disables the detector "
            "(SIGTERM via resilience.preempt still triggers lame-duck "
            "when serving_migration is on). FleetRouter kwarg "
            "lameduck_ms overrides.")
define_flag("serving_migration_retries", 3,
            "bounded resilience.retry RE-attempts for one live-"
            "migration snapshot transfer (KVPageTransport."
            "ship_snapshot) after a transient ConnectionError — incl. "
            "the injected router_migration_transient fault site. "
            "Exhausting the budget writes one MigrationError "
            "(PDT-E025) flight record and falls back to the PR17 cold "
            "requeue (demand counted once). N retries = N+1 attempts; "
            "0 disables retry. FleetRouter kwarg migration_retries "
            "overrides.")
define_flag("dp_overlap_grad_sync", False,
            "overlap-scheduled bucketed DP gradient sync "
            "(distributed/overlap.py): DataParallel registers per-param "
            "hooks and issues one psum-mean per size-capped bucket as "
            "each bucket's grads finalize DURING backward, so the "
            "collectives hide behind the remaining backward compute; "
            "apply_collective_grads() drains the pending results. "
            "Bitwise-identical to the serialized sync. Off = the "
            "pre-overlap serialized path; DataParallel kwarg "
            "overlap_grad_sync overrides per instance. comm_ms / "
            "overlap_frac surface through the observability registry. "
            "PDT114 notes eager train loops that serialize the sync.")
define_flag("pp_overlap_p2p", True,
            "pipeline p2p/compute overlap (fleet/pipeline.py): issue "
            "each stage's ppermute activation/cotangent sends BEFORE "
            "the independent work of the same tick (output banking, "
            "leaf-grad accumulation) so XLA can run the ICI transfer "
            "under compute. Pure reordering of independent ops — "
            "values are bitwise-identical either way; off restores the "
            "send-last order for A/B timing.")
define_flag("train_glue_fusion", False,
            "fused residual-add+norm training glue kernels (ISSUE 19, "
            "ops/pallas/fused_residual_norm.py): GPT/LLaMA training "
            "forwards thread a pending-branch through the block stack "
            "so every (residual add, pre-norm) pair — and the final "
            "norm — runs as ONE fused fwd/bwd Pallas dispatch; BERT's "
            "post-LN pairs fuse in place. Train-mode only (eval/serving "
            "keep the unfused path and its numerics). Default off: a "
            "custom call is a fusion BARRIER and the standalone Pallas "
            "LN lost time in context for that reason (see "
            "nn/functional/norm.py) — the fused glue path ships dark "
            "until a cell prices it end to end. Numerics "
            "differ from the unfused chain by norm-formula ulps "
            "(two-pass variance vs "
            "E[x^2]-E[x]^2), so this is an A/B knob, not a "
            "bitwise-neutral toggle.")
# Spellings for the glue-fusion knob (same strict convention as
# kv_quant: dispatch count is a measured claim, so an unrecognized
# spelling must raise, never silently pick a path).
GLUE_FUSION_OFF_SPELLINGS = KV_QUANT_OFF_SPELLINGS
GLUE_FUSION_ON_SPELLINGS = KV_QUANT_ON_SPELLINGS
define_flag("train_remat", "",
            "default selective-remat policy for hapi.Model training "
            "(ISSUE 19): when Model.prepare(remat=None) and this flag "
            "is non-empty, every remat-capable transformer block of "
            "the network gets activation recompute with this "
            "jax.checkpoint policy ('full', 'dots_saveable', "
            "'dots_and_kernels_saveable', 'transformer_saveable'; an "
            "on-spelling like '1'/'true' means "
            "'dots_and_kernels_saveable' — keep matmul/flash outputs, "
            "recompute the cheap elementwise/norm chain). Gradients "
            "are bitwise-identical remat on/off; only the saved-"
            "residual set (static_peak_bytes) and the backward's "
            "recompute fraction move. '' = off (the model config's own "
            "recompute field still applies).")
define_flag("train_prefetch", True,
            "double-buffered host->device input staging in Model.fit "
            "(ISSUE 19): batch N+1 is split and device_put while step "
            "N is still in flight (the hook runs between the step's "
            "dispatch and its blocking loss readback), so the transfer "
            "hides under device compute instead of extending the step "
            "loop. Loss trajectories are bitwise-identical to the "
            "synchronous feed — only WHEN the conversion happens "
            "moves. train.input_wait_ms / train.input_overlap_frac "
            "surface through the observability registry; off restores "
            "the synchronous convert-inside-the-step feed. PDT121 "
            "notes custom train loops that stage batches synchronously "
            "with no prefetch knob in scope.")
define_flag("metrics", True,
            "observability runtime (paddle_tpu.observability): metrics "
            "registry recording, structured-event ring buffer, serving "
            "timelines, training step telemetry and flight-recorder "
            "dumps. PDTPU_METRICS=off makes every record call a "
            "near-no-op (one dict lookup) and restores the "
            "pre-observability behavior bitwise; counters that back "
            "the serving engine's stats contract are created with "
            "always=True and keep recording either way.")
define_flag("serving_slo", "",
            "declarative latency/goodput objectives for serving "
            "engines (ISSUE 14, observability/slo.py): a comma-"
            "separated spec string like "
            "'ttft_p95_ms=500,tpot_p99_ms=100,goodput=0.99' evaluated "
            "over sliding windows of the engine's own timeline "
            "histograms with multi-window burn-rate alerting; a "
            "breach emits an slo.breach ring event and dumps a flight "
            "record. '' (default) arms nothing; engine kwarg slo "
            "overrides per instance (spec string or SLOSpec list). "
            "PDT117 notes engines with overload knobs but no SLO "
            "spec or watchdog.")
define_flag("serving_slo_window_s", 60.0,
            "slow/error-budget window for SLO burn-rate evaluation "
            "(observability/slo.py); the fast confirmation window is "
            "1/12 of it (the SRE two-window convention). SLOSpec "
            "kwargs fast_window_s/slow_window_s override per spec.")
define_flag("watchdog_stall_ms", 0.0,
            "stall-watchdog deadline (observability/watchdog.py): "
            "engine dispatches, DisaggServer handoffs, rpc invokes "
            "and Model.fit steps armed past this many ms without "
            "completing/heartbeating capture all thread stacks, dump "
            "the flight record + Chrome trace and emit watchdog.stall "
            "— the engine's dispatch additionally surfaces a coded "
            "EngineStallError (PDT-E020) instead of hanging its "
            "caller. 0 (default) = watchdog off; engine kwarg "
            "watchdog_ms overrides per instance. No-op with "
            "PDTPU_METRICS=off.")
define_flag("watchdog_poll_ms", 20.0,
            "stall-watchdog daemon-thread poll cadence; a stall is "
            "detected within deadline + one poll interval.")
define_flag("flight_keep", 40,
            "keep-last-K retention for flight records in "
            "PDTPU_FLIGHT_DIR (observability/events.py dump GC, "
            "mirroring CheckpointManager's keep-last-K): every dump "
            "deletes the oldest records (and their .trace.json/"
            ".stacks.txt companions) past this count. 0 = unbounded "
            "(the pre-ISSUE-14 behavior).")
define_flag("collective_timeout_ms", 0.0,
            "collective-watchdog deadline (resilience/elastic_train.py "
            "+ observability/watchdog.py): Group.psum_mean, "
            "DataParallel.apply_collective_grads, pipeline "
            "forward/train_batch dispatches and the elastic "
            "supervisor's store-backed allreduce armed past this many "
            "ms raise a coded CollectiveTimeoutError (PDT-E021) with "
            "thread stacks in a flight record instead of hanging every "
            "survivor behind a dead peer. 0 (default) = off; size the "
            "deadline above the worst case INCLUDING first compiles "
            "(an interrupt landing mid-compile aborts work that would "
            "have been cached). FleetSupervisor kwarg "
            "collective_timeout_ms overrides per instance.")
define_flag("elastic_snapshot_every", 50,
            "buddy in-memory snapshot cadence (resilience/"
            "elastic_train.py): every N optimizer steps each rank "
            "snapshots model/optimizer/RNG state to host memory and "
            "replicates it to its buddy rank asynchronously off the "
            "step path. 0 = snapshots off (recovery falls back to the "
            "newest COMPLETE CheckpointManager version); "
            "FleetSupervisor kwarg snapshot_every overrides.")
define_flag("elastic_buddy", 1,
            "buddy offset for in-memory snapshot replication: rank r "
            "replicates to rank (r + offset) % world "
            "(resilience/elastic_train.py). The dead rank's state is "
            "restored from its buddy's replica; only when the buddy is "
            "also gone does recovery read the on-disk checkpoint.")
define_flag("metrics_log_every", 0,
            "training StepTimer one-line log cadence: every N train "
            "steps hapi.Model.fit logs step wall-time, tokens/sec, "
            "MFU estimate and retrace count through the "
            "'paddle_tpu.observability' logger. 0 (default) = no "
            "periodic log; the gauges/histograms record regardless.")
define_flag("while_grad_max_trip_count", 256,
            "trip bound for differentiable while_loop under jit capture "
            "(lowered to a masked lax.scan; XLA has no reverse-mode "
            "while). A loop still live after this many iterations warns "
            "at runtime and returns the bound-truncated carry.")


class _GradMode(threading.local):
    def __init__(self):
        self.enabled = True


_grad_mode = _GradMode()


def is_grad_enabled() -> bool:
    return _grad_mode.enabled


def set_grad_enabled(enabled: bool) -> bool:
    old = _grad_mode.enabled
    _grad_mode.enabled = enabled
    return old


# --- global RNG (paddle.seed analog). Functional JAX PRNG under the hood:
# a mutable key that is split on every draw. The key lives in a Tensor and is
# read/written through the capture funnel, so a jit-captured train step
# threads the RNG state as a real input/output instead of baking a constant
# (the reference reaches the same end with stateful curand generators +
# seed/offset capture in CUDA graphs, SURVEY C30). ---
class _RNG:
    def __init__(self):
        self._key_var = None
        self._seed = 0

    def seed(self, s: int):
        import jax
        from .tensor import Tensor

        self._seed = int(s)
        key = jax.random.key_data(jax.random.PRNGKey(self._seed))
        if self._key_var is None:
            self._key_var = Tensor(key)
        else:
            self._key_var._write(key)

    def next_key(self):
        import jax

        if self._key_var is None:
            self.seed(0)
        key = jax.random.wrap_key_data(self._key_var._read())
        new_key, sub = jax.random.split(key)
        self._key_var._write(jax.random.key_data(new_key))
        return sub


default_rng = _RNG()


def seed(s: int):
    default_rng.seed(s)
    return default_rng
