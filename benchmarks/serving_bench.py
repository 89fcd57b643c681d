#!/usr/bin/env python
"""Serving measurements with roofline accounting (ISSUE 3; VERDICT r5
weak 4: "serving rows are launch-bound and have no roofline
accounting").  Reference bar: the fused serving kernels
``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``
and ``masked_multihead_attention_kernel.cu`` (SURVEY C12/C13).

Row schema (CHANGED in round 6 — consumers of the ``serving`` cache
entry note):

    batch, prompt_len, new_tokens, kv_cache, decode_window  — config
    ms_per_token       — wall per decode step (per-request latency)
    tokens_per_sec     — batch * new_tokens / wall
    wall_s             — best-of-3 wall time
    roofline_ms        — HBM-roofline target for one decode step:
                         (weight bytes + KV bytes read) / device HBM
                         bandwidth.  Decode is bandwidth-bound, so this
                         is the "as fast as the hardware allows" floor.
    roofline_x         — ms_per_token / roofline_ms (1.0 = at roofline)
    launch_ms          — measured per-dispatch round-trip cost times
                         dispatches-per-token (prefill + one scalar
                         step + ceil(new/K) windows, amortized)
    launch_share       — launch_ms / ms_per_token: how much of the row
                         is fixed dispatch overhead rather than device
                         work (VERDICT r5: ~4.4 of 9.05 ms at K=16)

plus a ``continuous_mixed`` row: a mixed-arrival workload (staggered
prompt/output lengths) through ``inference.ContinuousBatchingEngine``
— admissions ragged-batched with ongoing decodes, retirements
returning pages to the free list.  Its ``tokens_per_sec`` is the
continuous-batching throughput claim and must beat the fixed-batch
``paged_b8`` row to justify the scheduler.

plus an ``overload`` row (ISSUE 5): the same engine driven PAST its
capacity — page pool sized below the arrival working set, a bounded
admission queue, and tight deadlines on a slice of the requests — so
the overload policies (preempt-and-requeue, reject, timeout) are what
is being measured.  Reports ``goodput_tokens_per_sec`` (tokens of
normally-finished requests only), ``preemptions``, ``timeouts``,
``rejected`` and ``completed_ok``; a lab engine crashes on this
workload, a serving engine degrades and the row quantifies the
degradation.

plus two QUANT rows (ISSUE 7) whose roofline is recomputed from the
QUANTIZED bytes — the whole point of the int8 paths is to lower the
bandwidth floor itself, so the target column must move with them:

* ``quant_b8`` — the fixed-batch engine workload twice over identical
  traffic, ``kv_quant`` off then on (int8 KV pages + in-kernel
  dequant): per-token latency both ways, ``roofline_ms`` from int8+
  scale KV bytes, ``kv_page_bytes`` on/off (the halved-bytes claim),
  ``pages_per_request``, and the ``roofline_x`` delta vs the fp twin.
* ``weight_only_b1`` — ``generate(kv_cache='paged')`` on a
  ``weight_only_quantize``d model (int8 weights through the Pallas
  fused dequant-matmul) vs the same fp model: ms/token both ways,
  ``roofline_ms`` from int8 weight bytes + per-channel scales, and the
  weight-byte ratio.

plus a ``shared_prefix`` row (ISSUE 6): a system-prompt-heavy workload
(~90% of arrivals share a long prefix) through the engine with the
cross-request KV prefix cache (``inference/prefix_cache.py``) on vs.
off.  Reports the ROADMAP measure directly:
``prefill_tokens_computed`` vs. ``prefill_tokens_requested`` (the
saved fraction is the cache's compute win), mean time-to-first-token
with and without the cache, plus ``cache_hits``/``cache_hit_tokens``/
``evictions``.  The CPU tiny-model smoke
(``tests/test_serving_engine.py``) validates the accounting; absolute
times are TPU-measured.

plus a ``speculative`` row (ISSUE 9): a repetitive-text workload
(prompts tile a short motif, the regime where the model-free n-gram /
prompt-lookup proposer finds its continuations in context) driven
twice over identical traffic — ``spec_decode`` off (plain decode) then
on.  Reports ``accepted_tokens_per_step`` (the verify multiplier: mean
tokens emitted per slot per verify dispatch, from the engine's
``spec_accepted_per_step`` histogram), ``spec_accept_rate``,
tokens/sec both ways, and the ``outputs_equal`` gate — greedy
speculative output must be BITWISE the plain stream, so speculation
can only ever move throughput, never tokens.  The n-gram proposer runs
on the CPU smoke (``tests/test_speculative.py``); absolute times are
TPU claims.

plus ``tp2``/``tp4`` rows (ISSUE 13): the fixed-batch engine workload
single-device vs TP-sharded over a 2/4-device mesh axis
(``ContinuousBatchingEngine(mesh=)`` — weights column/row split per
the canonical Megatron rules, KV pools sharded by kv-head, one psum
at the attention output and MLP reduce).  The TP roofline is the
PER-DEVICE floor (``roofline_ms / tp``: each shard reads 1/tp of the
weight and KV bytes) and ``outputs_equal`` gates token-identical
greedy streams.

plus a ``disagg`` row (ISSUE 13): a latency class (long decodes)
alone and under a concurrent prefill storm, colocated vs
``inference.DisaggServer`` (prefill and decode worker groups with
the KV-page handoff).  Reports decode ``tpot_p99_ms`` for all four
cells — the claim is that the disagg decode group's p99 stays flat
under the storm while the colocated engine's tracks it — plus
``handoff_ms_avg``, ``transfer_bytes``, ``handoffs`` from the
coordinator's registry.

plus a ``metrics_overhead`` micro-row (ISSUE 8): identical engine
traffic with ``PDTPU_METRICS`` on vs off, reporting the tokens/sec
delta — the always-on observability runtime's <= 3% cost claim.  The
``continuous_mixed``/``overload``/``shared_prefix`` rows' TTFT/TPOT/
queue-time columns are derived from the engine's OWN event timelines
(``engine.metrics()``, ``paddle_tpu/observability/serving.py``)
instead of ad-hoc host timers: prefill chunks and decodes share one
ragged dispatch, so phase attribution must come from engine events.
Since ISSUE 14 the on half also arms the SLO guardrails + stall
watchdog (``slo=``/``watchdog_ms=``), so the overhead claim covers
judgment-layer cost too, and the ``continuous_mixed``/``overload``/
``disagg`` rows carry ``slo_ok``/``budget_burn`` columns — the SLO
engine's verdict (all objectives met; worst slow-window burn rate)
on the traffic the row measured, from the SAME percentile math the
report columns use (``observability.metrics.percentile_from_counts``).

Results persist via benchmarks/measured_cache.py and surface as a
compact ``serving`` entry in bench.py's enriched record and in
BASELINE.md.  Run standalone on the real chip, from the checkout's root:

    PYTHONPATH=. python benchmarks/serving_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault(
    "PDTPU_CACHE_DIR", os.path.join(_REPO, "benchmarks", "measured"))

# HBM bandwidth by device kind, GB/s (vendor specs; used for the
# roofline TARGET column, not for any measured number)
_HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}


def _hbm_gbps(dev) -> float:
    kind = str(getattr(dev, "device_kind", ""))
    for k, v in _HBM_GBPS.items():
        if k.lower() in kind.lower():
            return v
    return 819.0  # assume v5e-class when unknown


def _build_model():
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=2048, dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def _param_bytes(model) -> int:
    total = 0
    for p in model.parameters():
        n = 1
        for s in p.shape:
            n *= int(s)
        total += n * int(np.dtype(str(p.dtype).split(".")[-1]).itemsize)
    return total


def _kv_bytes_per_seq(cfg, avg_len, itemsize=4, scale_bytes=0) -> int:
    """KV bytes one sequence's cache reads per step; ``itemsize`` 1 +
    ``scale_bytes`` 4 is the int8 page-pool layout (one f32 absmax
    scale per head per token slot riding the side-pools)."""
    n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
    return 2 * cfg.num_layers * n_kv * avg_len \
        * (cfg.head_dim * itemsize + scale_bytes)


def _quant_param_bytes(model) -> int:
    """Weight bytes of a ``weight_only_quantize``d model: Linear
    weights at 1 byte + a 4-byte per-out-channel scale; everything
    else (embeddings, norms, biases) at float width."""
    from paddle_tpu.nn.layers import Linear
    total = _param_bytes(model)
    for layer in model.sublayers(include_self=True):
        if isinstance(layer, Linear):
            n_in, n_out = (int(s) for s in layer.weight.shape)
            total -= n_in * n_out * 4           # fp32 weight out...
            total += n_in * n_out + n_out * 4   # ...int8 + scales in
    return total


def roofline_ms(cfg, model, batch, prompt_len, new_tokens, gbps,
                kv_itemsize=4, kv_scale_bytes=0,
                param_bytes=None) -> float:
    """HBM floor for ONE decode step serving ``batch`` sequences: every
    weight byte read once, plus each sequence's (average-length) KV.
    The quant rows move the floor itself: ``kv_itemsize=1,
    kv_scale_bytes=4`` prices int8 KV pages, ``param_bytes`` overrides
    the weight term for int8 weights."""
    avg_len = prompt_len + new_tokens // 2
    bytes_step = (param_bytes if param_bytes is not None
                  else _param_bytes(model)) \
        + batch * _kv_bytes_per_seq(cfg, avg_len, kv_itemsize,
                                    kv_scale_bytes)
    return bytes_step / (gbps * 1e9) * 1e3


def _tl_node(eng, name) -> dict:
    node = eng.metrics()
    for part in ("serving." + name).split("."):
        node = node.get(part, {})
    return node


def _tl_pct(eng, name, q=0.99) -> float:
    """Percentile of one serving-timeline histogram — the SHARED
    ``observability.metrics.percentile_from_counts`` implementation
    (ISSUE 14: one home for the math, so the SLO engine's runtime
    judgment and this report column can never disagree on what a p99
    is).  The ``disagg`` row's decode-p99 claim reads this."""
    from paddle_tpu.observability.metrics import percentile_from_counts
    node = _tl_node(eng, name)
    return percentile_from_counts(node.get("buckets", []),
                                  node.get("counts", []),
                                  node.get("count", 0), q)


def _tl_mean(eng, name) -> float:
    """Mean of one serving-timeline histogram from ``engine.metrics()``
    (ISSUE 8): TTFT/TPOT columns come from the engine's OWN event
    timelines — the ragged mixed program batches prefill chunks and
    decodes of many requests into one dispatch, so host-side timer
    wrapping cannot attribute phases; the engine's scheduling events
    can.  Reads the snapshot's own ``mean`` (computed sum/count inside
    the histogram's locked ``_snap`` — the one implementation)."""
    return _tl_node(eng, name).get("mean", 0.0)


# default SLO objectives armed on the engine-driven rows (ISSUE 14):
# generous CPU-smoke-safe thresholds — the slo_ok/budget_burn columns
# REPORT the judgment layer's verdict on the measured traffic, they do
# not gate the bench.  The metrics_overhead row arms the same spec plus
# the stall watchdog, so its <= 3% claim covers guardrails-on serving.
_SLO_SPEC = ("ttft_p95_ms=2000,tpot_p99_ms=500,queue_p95_ms=5000,"
             "goodput=0.9")
_WATCHDOG_MS = 30000.0


def _slo_cols(eng) -> dict:
    """``slo_ok`` / ``budget_burn`` columns from an engine's armed SLO
    specs (all-ok verdict and the worst slow-window burn rate)."""
    sts = eng.slo_status()
    return {
        "slo_ok": bool(all(s["ok"] for s in sts)) if sts else True,
        "budget_burn": round(max((s["burn_slow"] for s in sts),
                                 default=0.0), 4),
    }


def measure_launch_ms() -> float:
    """Per-dispatch round-trip cost of this host<->device link: one
    trivial jitted program, timed submit-to-readback (the fixed cost
    every window/prefill dispatch pays regardless of device work)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    np.asarray(f(x))  # compile
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(f(x))
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def measure():
    import paddle_tpu as paddle
    from paddle_tpu.models.generation import generate

    import jax

    cfg, model = _build_model()
    dev = jax.devices()[0]
    gbps = _hbm_gbps(dev)
    launch = measure_launch_ms()
    rng = np.random.default_rng(0)
    rows = {}

    # whole-program audit bookkeeping (ISSUE 16): count findings only
    # from the serving programs this bench compiles
    from paddle_tpu import analysis as _analysis
    _analysis.audit_counts(reset=True)

    def finish(name, row, batch, prompt_len, new_tokens, window,
               n_dispatch):
        rl = roofline_ms(cfg, model, batch, prompt_len, new_tokens, gbps)
        lm = launch * n_dispatch / new_tokens
        row["roofline_ms"] = round(rl, 3)
        row["roofline_x"] = round(row["ms_per_token"] / rl, 1)
        row["launch_ms"] = round(lm, 3)
        row["launch_share"] = round(lm / row["ms_per_token"], 3)
        # host dispatches amortized per generated token: the
        # program-boundary count the windows amortize
        row["dispatches_per_token"] = round(n_dispatch / new_tokens, 3)
        rows[name] = row
        print(f"{name}: {row['ms_per_token']} ms/token "
              f"({row['tokens_per_sec']} tok/s, roofline x"
              f"{row['roofline_x']}, launch {row['launch_share']:.0%})",
              file=sys.stderr, flush=True)

    def run(name, batch, prompt_len, new_tokens, kv, window):
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size,
                         (batch, prompt_len)).astype(np.int32))
        kw = dict(max_new_tokens=new_tokens, temperature=0.0,
                  kv_cache=kv, decode_window=window)
        out = generate(model, ids, **kw)       # compile + warm
        np.asarray(out._read())
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = generate(model, ids, **kw)
            np.asarray(out._read())            # full sync readback
            best = min(best, time.perf_counter() - t0)
        ms_tok = best * 1e3 / new_tokens
        row = {
            "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens, "kv_cache": kv,
            "decode_window": window,
            "ms_per_token": round(ms_tok, 2),
            "tokens_per_sec": round(batch * new_tokens / best, 1),
            "wall_s": round(best, 3),
        }
        # dispatches: prefill + first scalar step + scanned windows
        n_disp = 2 + -(-new_tokens // window)
        finish(name, row, batch, prompt_len, new_tokens, window, n_disp)

    # single-request latency rows: 128-token prompt, 64 new tokens
    run("dense_b1", 1, 128, 64, "dense", 16)
    run("paged_b1", 1, 128, 64, "paged", 16)
    # multi-request batched decode over the page pools: 8 concurrent
    # sequences through one compiled windowed-decode program (the
    # fixed-batch bar continuous_mixed has to beat)
    run("paged_b8", 8, 128, 64, "paged", 16)
    # long-context serving check: 1024-token prompt, paged
    run("paged_b1_long", 1, 1024, 64, "paged", 16)
    rows["continuous_mixed"] = _measure_continuous(
        cfg, model, gbps, launch)
    rows["overload"] = _measure_overload(cfg, model)
    rows["shared_prefix"] = _measure_shared_prefix(cfg, model)
    rows["quant_b8"] = _measure_quant(cfg, model, gbps)
    rows["weight_only_b1"] = _measure_weight_only(cfg, model, gbps)
    rows["speculative"] = _measure_speculative(cfg, model)
    rows["metrics_overhead"] = _measure_metrics_overhead(cfg, model)
    rows["tp2"] = _measure_tp(cfg, model, gbps, 2)
    rows["tp4"] = _measure_tp(cfg, model, gbps, 4)
    rows["disagg"] = _measure_disagg(cfg, model)
    rows["fleet"] = _measure_fleet(cfg, model)
    # migration columns (ISSUE 20) ride the fleet row: drain latency
    # both ways, warm pages shipped, and the bitwise gate
    mig = _measure_migration(cfg, model)
    rows["fleet"].update({
        "drain_ms_migrate": mig["drain_ms_migrate"],
        "drain_ms_wait": mig["drain_ms_wait"],
        "migrated_pages": mig["migrated_pages"],
        "prefill_tokens_saved": mig["prefill_tokens_saved"],
        "outputs_equal_migration": mig["outputs_equal"]
        and mig["pages_leaked"] == 0})
    # per-code finding counts from every serving program compiled above
    # (engine caches, decode windows, TP wrappers); the regression
    # sentinel judges PDT* leaves lower-is-better
    rows["analysis"] = {"findings": _analysis.audit_counts()}
    return rows


def _mixed_workload(rng, n_requests, prompt_range, new_range):
    """Staggered arrivals with ragged prompt/output lengths — the mix a
    static batch cannot serve without padding every request to the
    longest."""
    return [(int(rng.integers(*prompt_range)),
             int(rng.integers(*new_range)))
            for _ in range(n_requests)]


def _measure_continuous(cfg, model, gbps, launch, slots=8,
                        max_seq_len=512, prompt_range=(32, 257),
                        new_range=(16, 65), n_requests=16,
                        page_size=16, decode_window=16,
                        prefill_chunk=128):
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(1)
    specs = _mixed_workload(rng, n_requests, prompt_range, new_range)

    def drive():
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, slo=_SLO_SPEC)
        # staggered arrivals: half queued up front, the rest trickling
        # in while earlier requests decode (admissions mid-stream)
        pending = list(specs)
        for p_len, n_new in pending[:len(pending) // 2]:
            eng.add_request(
                rng.integers(0, cfg.vocab_size, p_len).astype(np.int32),
                n_new)
        pending = pending[len(pending) // 2:]
        t0 = time.perf_counter()
        while eng.has_work or pending:
            if pending and eng.stats["steps"] % 2 == 0:
                p_len, n_new = pending.pop(0)
                eng.add_request(
                    rng.integers(0, cfg.vocab_size,
                                 p_len).astype(np.int32), n_new)
            eng.step()
        wall = time.perf_counter() - t0
        return eng, wall

    eng, _ = drive()                 # compile + warm (both programs)
    eng, wall = drive()
    toks = eng.stats["tokens_generated"]
    ms_tok = wall * 1e3 / max(toks / slots, 1)   # per-slot latency-ish
    avg_prompt = int(np.mean([s[0] for s in specs]))
    avg_new = int(np.mean([s[1] for s in specs]))
    rl = roofline_ms(cfg, model, slots, avg_prompt, avg_new, gbps)
    n_disp = eng.stats["decode_dispatches"]
    lm = launch * n_disp / max(toks / slots, 1)
    row = {
        "batch": slots, "prompt_len": avg_prompt, "new_tokens": avg_new,
        "kv_cache": "paged", "decode_window": decode_window,
        "requests": len(specs),
        "ms_per_token": round(ms_tok, 2),
        "tokens_per_sec": round(toks / wall, 1),
        "wall_s": round(wall, 3),
        "roofline_ms": round(rl, 3),
        "roofline_x": round(ms_tok / rl, 1),
        "launch_ms": round(lm, 3),
        "launch_share": round(min(lm / ms_tok, 1.0), 3),
        "dispatches_per_token": round(n_disp / max(toks, 1), 3),
        "pages_allocated": eng.stats["pages_allocated"],
        "peak_pages_in_use": eng.stats["peak_pages_in_use"],
        # per-request latency columns from the engine timelines
        "ttft_ms_avg": round(_tl_mean(eng, "ttft_ms"), 2),
        "tpot_ms_avg": round(_tl_mean(eng, "tpot_ms"), 2),
        "queue_ms_avg": round(_tl_mean(eng, "queue_ms"), 2),
        # SLO judgment on the measured traffic (ISSUE 14)
        **_slo_cols(eng),
    }
    print(f"continuous_mixed: {row['tokens_per_sec']} tok/s over "
          f"{row['requests']} staggered requests (TTFT "
          f"{row['ttft_ms_avg']} ms, TPOT {row['tpot_ms_avg']} ms)",
          file=sys.stderr, flush=True)
    return row


def _measure_overload(cfg, model, slots=8, max_seq_len=512,
                      prompt_range=(32, 257), new_range=(16, 65),
                      n_requests=24, page_size=16, decode_window=16,
                      prefill_chunk=128, max_queue=8,
                      deadline_every=6, deadline_ms=300.0):
    """Drive the engine PAST capacity and measure the degradation the
    overload policies buy: the page pool holds ~55% of the slots'
    worst-case working set (growth preempts), the queue is bounded
    with policy 'reject' (arrivals past depth shed), and every
    ``deadline_every``-th request carries a tight deadline."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(2)
    specs = _mixed_workload(rng, n_requests, prompt_range, new_range)
    np_per_seq = -(-max_seq_len // page_size)
    total_pages = 1 + int(slots * np_per_seq * 0.55)

    def drive():
        from paddle_tpu.core.errors import QueueFullError

        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, total_pages=total_pages,
            decode_window=decode_window, prefill_chunk=prefill_chunk,
            max_queue=max_queue, queue_policy="reject", slo=_SLO_SPEC)
        pending = list(enumerate(specs))
        done = {}
        rejected = 0
        t0 = time.perf_counter()
        while eng.has_work or pending:
            # arrivals outpace service: two per engine step
            for _ in range(2):
                if not pending:
                    break
                i, (p_len, n_new) = pending.pop(0)
                dl = (deadline_ms if i % deadline_every == 0
                      else None)
                try:
                    eng.add_request(
                        rng.integers(0, cfg.vocab_size,
                                     p_len).astype(np.int32),
                        n_new, deadline_ms=dl)
                except QueueFullError:  # load shed by design; anything
                    rejected += 1       # else must FAIL the bench
            for c in eng.step():
                done[c.request_id] = c
        wall = time.perf_counter() - t0
        return eng, done, rejected, wall

    drive()                            # compile + warm both programs
    eng, done, rejected, wall = drive()
    ok = [c for c in done.values() if c.ok]
    good_toks = sum(c.tokens.size for c in ok)
    st = eng.stats
    row = {
        "batch": slots, "kv_cache": "paged",
        "decode_window": decode_window,
        "requests": len(specs), "total_pages": total_pages,
        "max_queue": max_queue,
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(st["tokens_generated"] / wall, 1),
        "goodput_tokens_per_sec": round(good_toks / wall, 1),
        "completed_ok": len(ok),
        "preemptions": st["preemptions"],
        "timeouts": st["timeouts"],
        "rejected": rejected,
        "pages_leaked": st["pages_in_use"],   # must be 0
        # overload latency columns (engine timelines): queue time is
        # the column overload moves first, TTFT/TPOT show what the
        # admitted slice still got
        "ttft_ms_avg": round(_tl_mean(eng, "ttft_ms"), 2),
        "tpot_ms_avg": round(_tl_mean(eng, "tpot_ms"), 2),
        "queue_ms_avg": round(_tl_mean(eng, "queue_ms"), 2),
        # the overload row is exactly where the SLO layer earns its
        # keep: goodput burns budget as requests time out / shed
        **_slo_cols(eng),
    }
    print(f"overload: {row['goodput_tokens_per_sec']} good tok/s "
          f"({row['completed_ok']}/{row['requests']} ok, "
          f"{row['preemptions']} preempts, {row['timeouts']} timeouts, "
          f"{row['rejected']} rejected)", file=sys.stderr, flush=True)
    return row


def _measure_shared_prefix(cfg, model, slots=8, max_seq_len=512,
                           shared_len=192, tail_range=(8, 49),
                           new_tokens=32, n_requests=20,
                           hit_every=10, page_size=16,
                           decode_window=16, prefill_chunk=128,
                           seed=3, warm=True):
    """System-prompt-heavy traffic (ISSUE 6): every request but each
    ``hit_every``-th shares a ``shared_len``-token prefix (~90% prefix
    hit rate), driven twice — prefix cache OFF then ON — over identical
    arrivals.  The ROADMAP measure: prefill tokens computed vs.
    requested and mean TTFT at a high hit rate.  Works on the CPU tiny
    model too (the accounting smoke in tests/test_serving_engine.py
    uses it); absolute times only mean something on the TPU."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, cfg.vocab_size, shared_len).astype(np.int32)
    specs = []
    for i in range(n_requests):
        tail = rng.integers(0, cfg.vocab_size,
                            int(rng.integers(*tail_range))).astype(
                                np.int32)
        if i % hit_every == hit_every - 1:    # ~10% cold prompts
            prompt = rng.integers(
                0, cfg.vocab_size,
                shared_len + tail.size).astype(np.int32)
        else:
            prompt = np.concatenate([shared, tail])
        specs.append(prompt)

    def drive(prefix_cache):
        # TTFT comes from the engine's own timelines (ISSUE 8) — the
        # old host-side slot scan measured step-granular arrival of
        # out_toks, not the enqueue->first-token window the engine's
        # events pin exactly
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, prefix_cache=prefix_cache)
        pending = list(enumerate(specs))
        t0 = time.perf_counter()
        while eng.has_work or pending:
            for _ in range(2):                # staggered arrivals
                if not pending:
                    break
                _i, prompt = pending.pop(0)
                eng.add_request(prompt, new_tokens)
            eng.step()
        wall = time.perf_counter() - t0
        return eng, wall

    if warm:                                  # compile + warm (the CPU
        drive(False)                          # smoke skips the timing
    eng_off, wall_off = drive(False)          # rigor for speed)
    eng_on, wall_on = drive(True)
    st_on, st_off = eng_on.stats, eng_off.stats
    row = {
        "batch": slots, "kv_cache": "paged", "requests": n_requests,
        "shared_len": shared_len, "new_tokens": new_tokens,
        "hit_rate_cfg": round(1.0 - 1.0 / hit_every, 2),
        "prefill_tokens_requested": st_on["prefill_tokens_requested"],
        "prefill_tokens_computed": st_on["prefill_tokens_computed"],
        "prefill_saved_frac": round(
            1.0 - st_on["prefill_tokens_computed"]
            / max(st_on["prefill_tokens_requested"], 1), 3),
        "cache_hits": st_on["cache_hits"],
        "cache_hit_tokens": st_on["cache_hit_tokens"],
        "evictions": st_on["evictions"],
        "cached_pages": st_on["cached_pages"],
        "ttft_ms_avg": round(_tl_mean(eng_on, "ttft_ms"), 2),
        "ttft_ms_avg_nocache": round(_tl_mean(eng_off, "ttft_ms"), 2),
        "tpot_ms_avg": round(_tl_mean(eng_on, "tpot_ms"), 2),
        "tpot_ms_avg_nocache": round(_tl_mean(eng_off, "tpot_ms"), 2),
        "tokens_per_sec": round(
            st_on["tokens_generated"] / wall_on, 1),
        "tokens_per_sec_nocache": round(
            st_off["tokens_generated"] / wall_off, 1),
        "wall_s": round(wall_on, 3),
        "pages_leaked": st_on["pages_in_use"],   # must be 0
    }
    print(f"shared_prefix: {row['prefill_saved_frac']:.0%} prefill "
          f"saved ({row['prefill_tokens_computed']}/"
          f"{row['prefill_tokens_requested']} tokens computed), TTFT "
          f"{row['ttft_ms_avg']} ms vs {row['ttft_ms_avg_nocache']} ms "
          f"uncached", file=sys.stderr, flush=True)
    return row


def _measure_quant(cfg, model, gbps, slots=8, prompt_len=128,
                   new_tokens=64, page_size=16, decode_window=16,
                   prefill_chunk=128, max_seq_len=512, q_block=8,
                   seed=4, warm=True):
    """ISSUE 7 ``quant_b8``: the fixed-batch engine workload driven
    twice over IDENTICAL traffic — ``kv_quant`` off (the fp twin) then
    on (int8 KV pages, in-kernel dequant).  The roofline for the quant
    half is recomputed from the quantized bytes (int8 data + f32
    per-slot scales), because lowering that floor is the optimization's
    claim; ``kv_page_bytes`` on/off carries the halved-bytes
    acceptance number and ``outputs_equal`` pins token-identical greedy
    streams.  Works on the CPU tiny models for the accounting smoke;
    absolute times are TPU claims."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_len).astype(np.int32)
               for _ in range(slots)]

    def drive(kv_quant):
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, q_block=q_block,
            kv_quant=kv_quant)
        rids = [eng.add_request(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return eng, [done[r].sequence for r in rids], wall

    if warm:
        drive(False)
        drive(True)
    eng_fp, out_fp, wall_fp = drive(False)
    eng_q, out_q, wall_q = drive(True)
    toks = eng_q.stats["tokens_generated"]
    toks_fp = eng_fp.stats["tokens_generated"]
    ms_fp = wall_fp * 1e3 / max(toks_fp / slots, 1)
    ms_q = wall_q * 1e3 / max(toks / slots, 1)
    rl_fp = roofline_ms(cfg, model, slots, prompt_len, new_tokens, gbps)
    rl_q = roofline_ms(cfg, model, slots, prompt_len, new_tokens, gbps,
                       kv_itemsize=1, kv_scale_bytes=4)
    row = {
        "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "kv_cache": "paged",
        "decode_window": decode_window, "kv_quant": True,
        "ms_per_token": round(ms_q, 2),
        "tokens_per_sec": round(toks / wall_q, 1),
        "wall_s": round(wall_q, 3),
        "ms_per_token_fp": round(ms_fp, 2),
        # 6-decimal rooflines: the quant row's claim is rl_q < rl_fp,
        # which 3 decimals would erase for the CPU tiny-model smoke
        "roofline_ms": round(rl_q, 6),
        "roofline_ms_fp": round(rl_fp, 6),
        "roofline_x": round(ms_q / rl_q, 1),
        "roofline_x_fp": round(ms_fp / rl_fp, 1),
        "kv_page_bytes": eng_q.stats["kv_page_bytes"],
        "kv_page_bytes_fp": eng_fp.stats["kv_page_bytes"],
        "kv_bytes_ratio": round(eng_q.stats["kv_page_bytes"]
                                / eng_fp.stats["kv_page_bytes"], 3),
        "pages_per_request": round(
            eng_q.stats["pages_allocated"] / slots, 1),
        "outputs_equal": all(
            np.array_equal(a, b) for a, b in zip(out_q, out_fp)),
        "pages_leaked": eng_q.stats["pages_in_use"],   # must be 0
    }
    print(f"quant_b8: {row['ms_per_token']} ms/token vs "
          f"{row['ms_per_token_fp']} fp (roofline x{row['roofline_x']}"
          f" vs x{row['roofline_x_fp']}, kv bytes x"
          f"{row['kv_bytes_ratio']}, outputs_equal="
          f"{row['outputs_equal']})", file=sys.stderr, flush=True)
    return row


def _measure_weight_only(cfg, model, gbps, prompt_len=128,
                         new_tokens=64, seed=5, qmodel=None,
                         warm=True):
    """ISSUE 7 ``weight_only_b1``: single-request paged decode on a
    ``weight_only_quantize``d twin of the bench model — every Linear
    routed through the Pallas fused dequant-matmul — vs the fp model on
    the same prompt.  The roofline weight term is recomputed from int8
    weight + per-channel scale bytes (the weight-byte floor is what
    weight-only quantization buys at batch 1)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.generation import generate
    from paddle_tpu.quantization import weight_only_quantize

    if qmodel is None:
        # deterministic twin: same seed + config rebuilds the weights
        paddle.seed(0)
        qmodel = weight_only_quantize(type(model)(cfg))
        qmodel.eval()
    rng = np.random.default_rng(seed)
    ids = paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (1, prompt_len)).astype(np.int32))

    def drive(m):
        kw = dict(max_new_tokens=new_tokens, temperature=1.0,
                  kv_cache="paged", decode_window=16)
        out = generate(m, ids, **kw)
        np.asarray(out._read())
        best = float("inf")
        reps = 3 if warm else 1
        for _ in range(reps):
            t0 = time.perf_counter()
            out = generate(m, ids, **kw)
            np.asarray(out._read())
            best = min(best, time.perf_counter() - t0)
        return np.asarray(out._read()), best

    out_fp, wall_fp = drive(model)
    out_q, wall_q = drive(qmodel)
    pb_fp = _param_bytes(model)
    pb_q = _quant_param_bytes(model)
    rl_fp = roofline_ms(cfg, model, 1, prompt_len, new_tokens, gbps)
    rl_q = roofline_ms(cfg, model, 1, prompt_len, new_tokens, gbps,
                       param_bytes=pb_q)
    row = {
        "batch": 1, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "kv_cache": "paged", "decode_window": 16, "weight_only": "int8",
        "ms_per_token": round(wall_q * 1e3 / new_tokens, 2),
        "ms_per_token_fp": round(wall_fp * 1e3 / new_tokens, 2),
        "tokens_per_sec": round(new_tokens / wall_q, 1),
        "wall_s": round(wall_q, 3),
        "roofline_ms": round(rl_q, 6),
        "roofline_ms_fp": round(rl_fp, 6),
        "roofline_x": round(wall_q * 1e3 / new_tokens / rl_q, 1),
        "roofline_x_fp": round(wall_fp * 1e3 / new_tokens / rl_fp, 1),
        "weight_bytes": pb_q,
        "weight_bytes_fp": pb_fp,
        "weight_bytes_ratio": round(pb_q / pb_fp, 3),
        "outputs_equal": bool(np.array_equal(out_q, out_fp)),
    }
    print(f"weight_only_b1: {row['ms_per_token']} ms/token vs "
          f"{row['ms_per_token_fp']} fp (weight bytes x"
          f"{row['weight_bytes_ratio']}, roofline x{row['roofline_x']}"
          f" vs x{row['roofline_x_fp']})", file=sys.stderr, flush=True)
    return row


def _measure_speculative(cfg, model, slots=4, max_seq_len=512,
                         prompt_len=64, motif_len=8, new_tokens=48,
                         n_requests=8, spec_k=4, page_size=16,
                         decode_window=16, prefill_chunk=128,
                         q_block=8, seed=7, warm=True):
    """ISSUE 9 ``speculative`` row: repetitive-text traffic (each
    prompt tiles its own short motif) through the engine twice over
    IDENTICAL arrivals — ``spec_decode`` off, then on with the
    model-free n-gram proposer.  The verify multiplier is
    ``accepted_tokens_per_step`` (mean tokens emitted per slot per
    verify dispatch); ``outputs_equal`` pins the bitwise-greedy claim.
    Works on the CPU tiny models (the accounting smoke in
    tests/test_speculative.py drives it); absolute times are
    TPU-measured."""
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(seed)
    prompts = []
    for _ in range(n_requests):
        motif = rng.integers(0, cfg.vocab_size,
                             motif_len).astype(np.int32)
        prompts.append(np.tile(motif, -(-prompt_len // motif_len))
                       [:prompt_len])

    def drive(spec):
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, q_block=q_block,
            spec_decode=spec, spec_k=spec_k)
        rids = [eng.add_request(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return eng, [done[r].sequence for r in rids], wall

    if warm:                       # compile + warm both program sets
        drive(False)
        drive(True)
    eng_off, out_off, wall_off = drive(False)
    eng_on, out_on, wall_on = drive(True)
    st = eng_on.stats
    row = {
        "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "kv_cache": "paged",
        "spec_k": spec_k, "proposer": "ngram",
        "requests": n_requests,
        "tokens_per_sec": round(
            st["tokens_generated"] / wall_on, 1),
        "tokens_per_sec_plain": round(
            eng_off.stats["tokens_generated"] / wall_off, 1),
        "wall_s": round(wall_on, 3),
        # mean tokens emitted per slot per verify dispatch — the
        # decode-throughput multiplier speculation buys
        "accepted_tokens_per_step": round(
            _tl_mean(eng_on, "spec_accepted_per_step"), 2),
        "spec_accept_rate": st["spec_accept_rate"],
        "spec_proposed": st["spec_proposed"],
        "spec_accepted": st["spec_accepted"],
        "dispatches": st["decode_dispatches"],
        "dispatches_plain": eng_off.stats["decode_dispatches"],
        "outputs_equal": all(
            np.array_equal(a, b) for a, b in zip(out_on, out_off)),
        "pages_leaked": st["pages_in_use"],   # must be 0
    }
    print(f"speculative: {row['accepted_tokens_per_step']} accepted "
          f"tokens/step (accept rate {row['spec_accept_rate']}), "
          f"{row['tokens_per_sec']} tok/s vs "
          f"{row['tokens_per_sec_plain']} plain, outputs_equal="
          f"{row['outputs_equal']}", file=sys.stderr, flush=True)
    return row


def _measure_tp(cfg, model, gbps, tp, slots=8, prompt_len=128,
                new_tokens=64, page_size=16, decode_window=16,
                prefill_chunk=128, q_block=8, max_seq_len=512, seed=8,
                warm=True):
    """ISSUE 13 ``tp2``/``tp4`` rows: the fixed-batch engine workload
    driven twice over IDENTICAL traffic — single-device, then
    TP-sharded over a ``tp``-device mesh axis (weights column/row
    split, KV pools sharded by kv-head, one psum at the attention
    output and MLP reduce).  The roofline for the TP half is the
    PER-DEVICE floor: each shard reads ``1/tp`` of the weight and KV
    bytes, so the target column is ``roofline_ms / tp`` — the whole
    point of the cut is to move the floor itself.  ``outputs_equal``
    pins token-identical greedy streams.  Works on the CPU mesh for
    the accounting smoke; absolute times are TPU claims."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.inference import ContinuousBatchingEngine

    if len(jax.devices()) < tp:
        return {"skipped": f"needs {tp} devices, have "
                           f"{len(jax.devices())}"}
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("tp",))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_len).astype(np.int32)
               for _ in range(slots)]

    def drive(m):
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, q_block=q_block, mesh=m)
        rids = [eng.add_request(p, new_tokens) for p in prompts]
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        return eng, [done[r].sequence for r in rids], wall

    if warm:
        drive(None)
        drive(mesh)
    eng_1, out_1, wall_1 = drive(None)
    eng_tp, out_tp, wall_tp = drive(mesh)
    toks = eng_tp.stats["tokens_generated"]
    ms_1 = wall_1 * 1e3 / max(eng_1.stats["tokens_generated"] / slots,
                              1)
    ms_tp = wall_tp * 1e3 / max(toks / slots, 1)
    rl_1 = roofline_ms(cfg, model, slots, prompt_len, new_tokens, gbps)
    rl_tp = rl_1 / tp                  # per-device bytes: weights + KV
    row = {                            # shards both split tp ways
        "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "kv_cache": "paged",
        "decode_window": decode_window, "tp": tp,
        "ms_per_token": round(ms_tp, 2),
        "tokens_per_sec": round(toks / wall_tp, 1),
        "wall_s": round(wall_tp, 3),
        "ms_per_token_1dev": round(ms_1, 2),
        "roofline_ms": round(rl_tp, 6),
        "roofline_ms_1dev": round(rl_1, 6),
        "roofline_x": round(ms_tp / rl_tp, 1),
        "roofline_x_1dev": round(ms_1 / rl_1, 1),
        "outputs_equal": all(
            np.array_equal(a, b) for a, b in zip(out_tp, out_1)),
        "pages_leaked": eng_tp.stats["pages_in_use"],   # must be 0
    }
    print(f"tp{tp}: {row['ms_per_token']} ms/token vs "
          f"{row['ms_per_token_1dev']} on 1 dev (per-device roofline "
          f"x{row['roofline_x']}, outputs_equal="
          f"{row['outputs_equal']})", file=sys.stderr, flush=True)
    return row


def _measure_disagg(cfg, model, slots=6, prompt_len=64, new_tokens=48,
                    storm_prompt=256, storm_new=4, n_latency=6,
                    n_storm=12, page_size=16, decode_window=16,
                    prefill_chunk=128, max_seq_len=512, q_block=8,
                    seed=9, warm=True):
    """ISSUE 13 ``disagg`` row: a latency class (medium prompt, long
    decode) served alone and then under a concurrent PREFILL STORM
    (long prompts, trivial decode) — first on one colocated engine,
    then through ``inference.DisaggServer`` (prefill and decode worker
    groups with the KV-page handoff).  The claim is the decode-p99
    shape: colocated p99 tracks the storm (prefill chunks steal mixed
    dispatches from residents' decodes), the disagg decode group's
    stays flat because prefill compute is physically elsewhere.
    Reports ``tpot_p99_ms_*`` for all four cells plus the handoff
    accounting (``handoff_ms_avg``, ``transfer_bytes``,
    ``handoffs``)."""
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      DisaggServer)

    rng = np.random.default_rng(seed)
    lat = [rng.integers(0, cfg.vocab_size,
                        prompt_len).astype(np.int32)
           for _ in range(n_latency)]
    storm = [rng.integers(0, cfg.vocab_size,
                          storm_prompt).astype(np.int32)
             for _ in range(n_storm)]
    kw = dict(max_slots=slots, page_size=page_size,
              max_seq_len=max_seq_len, decode_window=decode_window,
              prefill_chunk=prefill_chunk, q_block=q_block)

    def drive_colocated(with_storm):
        eng = ContinuousBatchingEngine(model, **kw)
        for p in lat:
            eng.add_request(p, new_tokens)
        pending = list(storm) if with_storm else []
        while eng.has_work or pending:
            if pending:                        # storm arrivals: 2/step
                for _ in range(2):
                    if pending:
                        eng.add_request(pending.pop(0), storm_new)
            eng.step()
        return eng

    def drive_disagg(with_storm):
        # the decode group carries the SLO spec: disaggregation exists
        # to protect decode TPOT tails, so that is where the judgment
        # layer watches (slo_ok/budget_burn columns below)
        srv = DisaggServer(model, prefill_kwargs=dict(kw),
                           decode_kwargs=dict(kw, slo=_SLO_SPEC))
        for p in lat:
            srv.add_request(p, new_tokens)
        pending = list(storm) if with_storm else []
        while srv.has_work or pending:
            if pending:
                for _ in range(2):
                    if pending:
                        srv.add_request(pending.pop(0), storm_new)
            srv.step()
        return srv

    if warm:
        drive_colocated(True)
        drive_disagg(True)
    co_calm = drive_colocated(False)
    co_storm = drive_colocated(True)
    dg_calm = drive_disagg(False)
    dg_storm = drive_disagg(True)
    st = dg_storm.stats
    dec = dg_storm.decode_group[0]
    row = {
        "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "kv_cache": "paged",
        "storm_prompt": storm_prompt, "storm_requests": n_storm,
        "requests": n_latency,
        # the p99 grid: colocated decode latency degrades under the
        # storm; the disagg decode group's should not
        "tpot_p99_ms_colocated": round(
            _tl_pct(co_calm, "tpot_ms"), 3),
        "tpot_p99_ms_colocated_storm": round(
            _tl_pct(co_storm, "tpot_ms"), 3),
        "tpot_p99_ms_disagg": round(
            _tl_pct(dg_calm.decode_group[0], "tpot_ms"), 3),
        "tpot_p99_ms_disagg_storm": round(
            _tl_pct(dec, "tpot_ms"), 3),
        "tpot_ms_avg_colocated_storm": round(
            _tl_mean(co_storm, "tpot_ms"), 3),
        "tpot_ms_avg_disagg_storm": round(
            _tl_mean(dec, "tpot_ms"), 3),
        "handoffs": st["handoffs"],
        "transfer_bytes": st["handoff_bytes"],
        "handoff_ms_avg": round(
            _disagg_handoff_mean(dg_storm), 3),
        "requeues": st["requeues"],
        "pages_leaked": (st["prefill_pages_in_use"]
                         + st["decode_pages_in_use"]),   # must be 0
        # decode-group SLO verdict under the storm (ISSUE 14)
        **_slo_cols(dec),
    }
    print(f"disagg: decode p99 {row['tpot_p99_ms_disagg']} -> "
          f"{row['tpot_p99_ms_disagg_storm']} ms under storm (vs "
          f"colocated {row['tpot_p99_ms_colocated']} -> "
          f"{row['tpot_p99_ms_colocated_storm']}), "
          f"{row['handoffs']} handoffs, "
          f"{row['transfer_bytes']} bytes, "
          f"{row['handoff_ms_avg']} ms/handoff", file=sys.stderr,
          flush=True)
    return row


def _merged_tl_pct(engines, name, q=0.95) -> float:
    """Percentile of one timeline histogram MERGED across replicas:
    the fixed log-spaced buckets are identical on every registry, so
    fleet-wide tails are a bucket-count sum away (the same shared
    ``percentile_from_counts`` math as the single-engine columns)."""
    from paddle_tpu.observability.metrics import percentile_from_counts
    buckets, counts, total = [], [], 0
    for eng in engines:
        node = _tl_node(eng, name)
        if not node.get("count"):
            continue
        if not buckets:
            buckets = list(node["buckets"])
            counts = [0] * len(node["counts"])
        counts = [a + b for a, b in zip(counts, node["counts"])]
        total += node["count"]
    return percentile_from_counts(buckets, counts, total, q)


def _measure_fleet(cfg, model, slots=4, prompt_len=64, new_tokens=24,
                   shared_groups=4, group_size=4, n_light=4,
                   light_new=8, page_size=16, decode_window=16,
                   prefill_chunk=64, max_seq_len=256, q_block=8,
                   kill_step=3, seed=11, warm=True):
    """ISSUE 17 ``fleet`` row: the multi-replica router's three claims
    measured on one skewed-tenant workload (a ``storm`` tenant flooding
    shared-prefix groups plus a light ``interactive`` tenant).

    * CAPACITY — the same traffic through 4 routed replicas vs 1:
      fleet TTFT p95 (merged replica histograms) and goodput drop
      with fleet width.
    * AFFINITY — prefix-cache-aware placement vs round-robin on the
      same shared-prefix storm: fleet-wide cache-hit token fraction
      (affinity concentrates each group where its pages live; RR
      scatters them, so every replica re-prefills the prefix).
    * RECOVERY — a 3-replica fleet with one replica killed mid-decode:
      ``recover_ms`` (kill -> every affected request completed on a
      survivor), ``requeued``, ``outputs_equal`` vs the unfaulted run
      (greedy decode is batch-invariant, so this must be True) and
      ``pages_leaked`` on the survivors (must be 0)."""
    from paddle_tpu.inference import FleetRouter, TenantSpec
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(seed)
    prefix_len = prompt_len // 2
    groups = []
    for _ in range(shared_groups):
        prefix = rng.integers(0, cfg.vocab_size,
                              prefix_len).astype(np.int32)
        groups.append([np.concatenate([
            prefix, rng.integers(0, cfg.vocab_size,
                                 prompt_len - prefix_len)
            .astype(np.int32)]) for _ in range(group_size)])
    # leaders warm each group's prefix onto SOME replica; the storm is
    # the remaining members interleaved across groups (consecutive
    # arrivals from different groups — the placement decision affinity
    # must get right and round-robin gets right only by luck)
    leaders = [g[0] for g in groups]
    storm = [g[i] for i in range(1, group_size) for g in groups]
    light = [rng.integers(0, cfg.vocab_size,
                          prompt_len // 4).astype(np.int32)
             for _ in range(n_light)]
    kw = dict(max_slots=slots, page_size=page_size,
              max_seq_len=max_seq_len, decode_window=decode_window,
              prefill_chunk=prefill_chunk, q_block=q_block)
    tenants = [TenantSpec("storm", weight=1.0),
               TenantSpec("interactive", weight=4.0, priority=0)]

    def drive(n_replicas, affinity, kill=None):
        faults.clear()
        r = FleetRouter(model, replicas=n_replicas, replica_kwargs=kw,
                        tenants=tenants, affinity=affinity)
        done = {}
        # warm phase: the trie publishes pages at retirement, so each
        # group's leader runs to completion first — its prefix lands
        # on SOME replica's cache, which is the steady-state a fleet
        # front-end lives in (system prompts already resident)
        for p in leaders:
            r.add_request(p, new_tokens, tenant="storm")
        done.update(r.run())
        # storm phase: the rest arrive staggered 2/step
        pending = [(p, new_tokens, "storm") for p in storm]
        for i, p in enumerate(light):
            pending.insert(3 * i + 1, (p, light_new, "interactive"))
        affected, t_kill, t_rec, step = None, None, None, 0
        while r.has_work or pending:
            for _ in range(2):
                if pending:
                    p, n, t = pending.pop(0)
                    r.add_request(p, n, tenant=t)
            if kill is not None and step == kill:
                affected = set(r._by_name("r1").rids)
                faults.inject("router_replica_lost", "r1")
                t_kill = time.perf_counter()
            for c in r.step():
                done[c.request_id] = c
            if (affected is not None and t_rec is None
                    and affected <= set(done)):
                t_rec = time.perf_counter()
            step += 1
            assert step < 100000, "fleet bench wedged"
        rec_ms = ((t_rec - t_kill) * 1e3
                  if t_kill is not None and t_rec is not None else 0.0)
        return r, done, rec_ms, (len(affected) if affected else 0)

    if warm:
        drive(1, True)
    t0 = time.perf_counter()
    r4, d4, _, _ = drive(4, True)
    wall4 = time.perf_counter() - t0
    t0 = time.perf_counter()
    r1, d1, _, _ = drive(1, True)
    wall1 = time.perf_counter() - t0
    rrr, drr, _, _ = drive(4, False)

    def live_engines(r):
        return [rep.engine for rep in r._replicas
                if rep.state != "dead"]

    def hit_frac(r):
        hit = req = 0
        for e in live_engines(r):
            s = e.stats
            hit += s["cache_hit_tokens"]
            req += s["prefill_tokens_requested"]
        return hit / req if req else 0.0

    def goodput(r, done):
        ok = sum(1 for c in done.values()
                 if c.finish_reason in ("stop", "length"))
        return ok / len(done) if done else 0.0

    # recovery drill: 3 replicas, kill r1 mid-decode, compare to the
    # unfaulted 3-replica run request-by-request
    r3c, d3c, _, _ = drive(3, True)
    r3f, d3f, rec_ms, requeued = drive(3, True, kill=kill_step)
    outputs_equal = (sorted(d3c) == sorted(d3f) and all(
        np.array_equal(d3c[k].tokens, d3f[k].tokens) for k in d3c))
    leaked = sum(e.stats["pages_in_use"] for e in live_engines(r3f))

    row = {
        "replicas": 4, "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "requests": len(storm) + len(light), "kv_cache": "paged",
        "ttft_p95_ms_fleet4": round(
            _merged_tl_pct(live_engines(r4), "ttft_ms", 0.95), 3),
        "ttft_p95_ms_fleet1": round(
            _merged_tl_pct(live_engines(r1), "ttft_ms", 0.95), 3),
        "goodput_fleet4": round(goodput(r4, d4), 4),
        "goodput_fleet1": round(goodput(r1, d1), 4),
        "tokens_per_sec_fleet4": round(
            sum(e.stats["tokens_generated"]
                for e in live_engines(r4)) / wall4, 1),
        "tokens_per_sec_fleet1": round(
            sum(e.stats["tokens_generated"]
                for e in live_engines(r1)) / wall1, 1),
        "cache_hit_frac_affinity": round(hit_frac(r4), 4),
        "cache_hit_frac_rr": round(hit_frac(rrr), 4),
        "recover_ms": round(rec_ms, 3),
        "requeued": requeued,
        "deaths": r3f.stats["deaths"],
        "outputs_equal": bool(outputs_equal),
        "pages_leaked": int(leaked),   # must be 0
    }
    print(f"fleet: ttft p95 {row['ttft_p95_ms_fleet1']} -> "
          f"{row['ttft_p95_ms_fleet4']} ms at 4 replicas, cache-hit "
          f"{row['cache_hit_frac_rr']:.0%} (rr) -> "
          f"{row['cache_hit_frac_affinity']:.0%} (affinity), "
          f"replica kill: {row['requeued']} requeued, recovered in "
          f"{row['recover_ms']} ms, outputs_equal="
          f"{row['outputs_equal']}", file=sys.stderr, flush=True)
    return row


def _measure_migration(cfg, model, slots=4, prompt_len=64,
                       new_tokens=24, n_requests=6, page_size=16,
                       decode_window=16, prefill_chunk=64,
                       max_seq_len=256, q_block=8, drain_step=3,
                       seed=13, warm=True):
    """ISSUE 20 ``migration`` columns (merged onto the ``fleet`` row):
    graceful drain measured BOTH ways on one 2-replica workload —
    ``drain_ms_migrate`` (live migration on: residents ship warm over
    ``KVPageTransport`` and the drained replica parks as soon as the
    transfers land) vs ``drain_ms_wait`` (cold drain: the replica
    waits out every resident decode before parking).
    ``migrated_pages`` counts the KV pages that actually moved;
    ``prefill_tokens_saved`` prices them (pages * page_size — every
    shipped page is a page of already-computed tokens the destination
    did NOT recompute, exactly what the PR17 cold requeue would have
    re-prefilled); ``outputs_equal`` gates the row: both drained runs
    must be bitwise the undrained run (greedy decode is deterministic
    and batch-invariant, so migration is scheduling, never semantics).
    Absolute times are TPU claims; the CPU smoke gates semantics."""
    from paddle_tpu.inference import FleetRouter

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    kw = dict(max_slots=slots, page_size=page_size,
              max_seq_len=max_seq_len, decode_window=decode_window,
              prefill_chunk=prefill_chunk, q_block=q_block)

    def drive(drain, migration):
        r = FleetRouter(model, replicas=2, replica_kwargs=kw,
                        migration=migration)
        rids = [r.add_request(p, new_tokens) for p in prompts]
        done, step, t_drain, t_parked = {}, 0, None, None
        while r.has_work:
            if drain and step == drain_step:
                t_drain = time.perf_counter()
                r.drain("r0")
            for c in r.step():
                done[c.request_id] = c
            if (t_drain is not None and t_parked is None
                    and r.replica_states()["r0"] == "standby"):
                t_parked = time.perf_counter()
            step += 1
            assert step < 100000, "migration bench wedged"
        if t_drain is not None and t_parked is None:
            t_parked = time.perf_counter()
        drain_ms = ((t_parked - t_drain) * 1e3
                    if t_drain is not None else 0.0)
        return r, rids, done, drain_ms

    if warm:
        drive(False, False)
    _, rids0, base, _ = drive(False, False)       # no drain: the bar
    rm, rids_m, dm, ms_migrate = drive(True, True)
    rw, rids_w, dw, ms_wait = drive(True, False)
    outputs_equal = all(
        np.array_equal(base[a].tokens, dm[b].tokens)
        and np.array_equal(base[a].tokens, dw[c].tokens)
        for a, b, c in zip(rids0, rids_m, rids_w))
    leaked = sum(rep.engine.stats["pages_in_use"]
                 for rep in rm._replicas if rep.state != "dead")
    row = {
        "drain_ms_migrate": round(ms_migrate, 3),
        "drain_ms_wait": round(ms_wait, 3),
        "migrated_pages": int(rm.stats["migrated_pages"]),
        "prefill_tokens_saved": int(rm.stats["migrated_pages"]
                                    * page_size),
        "migration_failures": int(rm.stats["migration_failures"]),
        "outputs_equal": bool(outputs_equal),
        "pages_leaked": int(leaked),   # must be 0
    }
    print(f"migration: drain {row['drain_ms_wait']} ms (cold wait) -> "
          f"{row['drain_ms_migrate']} ms (live migrate), "
          f"{row['migrated_pages']} pages shipped warm "
          f"({row['prefill_tokens_saved']} prefill tokens saved), "
          f"outputs_equal={row['outputs_equal']}",
          file=sys.stderr, flush=True)
    return row


def _disagg_handoff_mean(srv) -> float:
    node = srv.metrics()
    for part in ("serving", "handoff_ms"):
        node = node.get(part, {})
    cnt = node.get("count", 0)
    return node.get("sum", 0.0) / cnt if cnt else 0.0


def _measure_metrics_overhead(cfg, model, slots=6, prompt_len=32,
                              new_tokens=24, page_size=16,
                              decode_window=8, prefill_chunk=64,
                              max_seq_len=128, q_block=8, reps=3,
                              n_requests=None, warm=True):
    """ISSUE 8 ``metrics_overhead``: IDENTICAL traffic through the
    engine with ``PDTPU_METRICS`` on vs off, reporting the tokens/sec
    delta.  The observability runtime's always-on claim is that the on
    state costs <= 3% tokens/sec on the serving hot loop — this row is
    the number behind that claim (best-of-``reps`` walls each way so
    scheduler noise doesn't masquerade as metric cost).  Since
    ISSUE 14 the engine runs with the SLO guardrails and the stall
    watchdog ARMED, so the gate covers judgment-layer cost too (both
    are metrics-flag-gated no-ops in the off half).  Runs on the CPU
    tiny models for the smoke test; the TPU measurement is the claim
    of record."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import ContinuousBatchingEngine

    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, cfg.vocab_size,
                            prompt_len).astype(np.int32)
               for _ in range(n_requests or 2 * slots)]

    def drive():
        # guardrails ARMED (ISSUE 14): the overhead claim covers SLO
        # evaluation + the per-dispatch watchdog arm/disarm, not just
        # bare metrics — they ride the existing event stream, so the
        # row must prove they add no per-token host sync.  With
        # metrics off both are no-ops, so the off half stays the
        # pre-observability baseline.
        eng = ContinuousBatchingEngine(
            model, max_slots=slots, page_size=page_size,
            max_seq_len=max_seq_len, decode_window=decode_window,
            prefill_chunk=prefill_chunk, q_block=q_block,
            slo=_SLO_SPEC, watchdog_ms=_WATCHDOG_MS)
        for p in prompts:
            eng.add_request(p, new_tokens)
        t0 = time.perf_counter()
        eng.run()
        wall = time.perf_counter() - t0
        return eng.stats["tokens_generated"], wall

    def timed(flag):
        paddle.set_flags({"metrics": flag})
        return drive()

    old = paddle.get_flags("metrics")["metrics"]
    try:
        if warm:                # compile + warm both flag states
            timed(False)
            timed(True)
        # INTERLEAVED best-of: alternate off/on within each rep so a
        # monotonic machine-load drift (cache warming, a background
        # compile, CPU frequency) biases both states equally instead
        # of charging the later state with it
        toks_off = toks_on = 0
        wall_off = wall_on = float("inf")
        for _ in range(reps):
            t, w = timed(False)
            if w < wall_off:
                toks_off, wall_off = t, w
            t, w = timed(True)
            if w < wall_on:
                toks_on, wall_on = t, w
    finally:
        paddle.set_flags({"metrics": old})
    tps_off = toks_off / wall_off
    tps_on = toks_on / wall_on
    row = {
        "batch": slots, "prompt_len": prompt_len,
        "new_tokens": new_tokens, "kv_cache": "paged",
        "decode_window": decode_window, "requests": len(prompts),
        "tokens_per_sec": round(tps_on, 1),
        "tokens_per_sec_off": round(tps_off, 1),
        "wall_s": round(wall_on, 3),
        "wall_s_off": round(wall_off, 3),
        # the acceptance number: fractional tokens/sec given up by
        # leaving metrics on (negative = noise floor; gate is <= 0.03)
        "overhead_frac": round(max(0.0, 1.0 - tps_on / tps_off), 4),
    }
    print(f"metrics_overhead: {row['tokens_per_sec']} tok/s on vs "
          f"{row['tokens_per_sec_off']} off "
          f"({row['overhead_frac']:.1%} overhead)", file=sys.stderr,
          flush=True)
    return row


# the serving rows' validity depends on the engine's scheduling layer
# and its policy knobs (core/state.py serving_* flags, resilience
# guard/retry), not just the kernels — include them in code_version so
# policy changes re-measure
FILES = ["benchmarks/serving_bench.py",
         "paddle_tpu/models/generation.py",
         "paddle_tpu/inference/engine.py",
         "paddle_tpu/inference/prefix_cache.py",
         "paddle_tpu/inference/speculative.py",
         # disaggregated/TP serving (ISSUE 13): the tp2/tp4/disagg
         # rows and every engine row's scheduling layer ride these
         "paddle_tpu/inference/distserve.py",
         # fleet router (ISSUE 17): the fleet row's placement, QoS and
         # replica-kill recovery all ride it
         "paddle_tpu/inference/router.py",
         "paddle_tpu/resilience/serving.py",
         # live migration (ISSUE 20): the fleet row's drain/migration
         # columns ride snapshot/restore + the preempt flag
         "paddle_tpu/resilience/preempt.py",
         "paddle_tpu/core/state.py",
         "paddle_tpu/ops/pallas/paged_attention.py",
         "paddle_tpu/ops/pallas/flash_attention.py",
         "paddle_tpu/ops/pallas/quant_matmul.py",
         "paddle_tpu/quantization/__init__.py",
         # the observability runtime rides the serving hot loop (event
         # emission + timeline observes per dispatch/token): edits to
         # it re-measure every serving row on the next TPU run
         "paddle_tpu/observability/metrics.py",
         "paddle_tpu/observability/events.py",
         "paddle_tpu/observability/serving.py",
         # dispatch tracing spans (ISSUE 12) ride every engine
         # dispatch: span cost is part of the metrics_overhead claim
         "paddle_tpu/observability/tracing.py",
         # SLO guardrails + stall watchdog (ISSUE 14) arm the
         # metrics_overhead row and feed the slo_ok/budget_burn
         # columns: their code must re-measure the serving rows
         "paddle_tpu/observability/slo.py",
         "paddle_tpu/observability/watchdog.py"]


def cached_rows(dev):
    """Previously measured serving rows for this device kind, or None
    (bench.py embeds these without re-measuring)."""
    import measured_cache as mc
    kind = str(getattr(dev, "device_kind", dev.platform))
    return mc.load(kind, "serving", mc.code_version(*FILES))


def main():
    import jax

    import measured_cache as mc
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("serving_bench: not on TPU; skipping", file=sys.stderr)
        return 0
    kind = str(getattr(dev, "device_kind", dev.platform))
    ver = mc.code_version(*FILES)
    rows = mc.load(kind, "serving", ver)
    if rows is None:
        rows = measure()
        mc.store(kind, "serving", ver, rows)
    print(json.dumps({"serving": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
