#!/usr/bin/env python
"""Driver benchmark: prints ONE JSON line
{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Headline: GPT-124M (BASELINE.md config-4 class) training throughput on one
chip — jit-compiled full train step (fwd + loss + bwd + AdamW), bf16 AMP O2,
activation recompute, executed as ONE dispatch per WINDOW_STEPS-step window
(jit.WindowRunner: scanned steps, pre-staged inputs — per-step host work on
the chip otherwise dominates). vs_baseline = achieved MFU /
0.40, the A100-parity north star of BASELINE.md (the reference publishes no
absolute numbers, so parity-with-Paddle-CUDA is expressed as matching 40%
model-FLOPs utilization on the local chip's peak).

Budget discipline (round-3 rc:124 postmortem): everything expensive that
is NOT the headline — kernel-rate calibration, ResNet50/BERT north-star
secondaries — is persisted in benchmarks/measured/ keyed by device kind +
a content hash of the code that produced it, and only re-measured when
that code changes. The flash-attention block autotune cache is likewise
repo-persisted (PDTPU_CACHE_DIR below): a fresh environment re-tuning
from scratch costs ~7 minutes of compiles.

TPU rules (.claude/skills/verify/SKILL.md): everything through the jit
path; no SIGKILL; single process owns the chip.
"""
from __future__ import annotations

import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))
# flash-attention autotune winners persist inside the repo (committed);
# ~/.cache is wiped between rounds and re-tuning costs minutes of compiles
os.environ.setdefault(
    "PDTPU_CACHE_DIR", os.path.join(_REPO, "benchmarks", "measured"))
sys.path.insert(0, os.path.join(_REPO, "benchmarks"))

import numpy as np

import measured_cache as mc

# bf16 peak FLOPs by device kind (per chip)
_PEAK = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

WINDOW_STEPS = 200  # steps per dispatch; see extra.host_overhead
# (r5: the per-window launch cost is ~71 ms fixed — K=50 left
# 1.4 ms/step of it in the number; K=200 amortizes to 0.36 ms
# while the staged int32 ids stay a few MB)


def _peak_flops(dev) -> float:
    kind = getattr(dev, "device_kind", "")
    for k, v in _PEAK.items():
        if k.lower() in str(kind).lower():
            return v
    return 197e12  # assume v5e-class when unknown


# every _cached entry is timed through this shared harness — a change
# here must invalidate all cached rows, or a regression in the timing
# path would re-report stale numbers as current measured evidence.
# bench.py itself is hashed at FUNCTION granularity (the measurement
# fns, passed per entry) so cosmetic bench edits — emit format, extra
# wiring — cannot cold the whole cache and blow the driver's budget.
_HARNESS_FILES = [
    "paddle_tpu/jit/multi_step.py",
    "paddle_tpu/optimizer/optimizer.py",
    # the fused multi-tensor optimizer path runs inside every training
    # row's compiled step: its code must cold the training caches
    "paddle_tpu/optimizer/flat.py",
    "paddle_tpu/ops/pallas/fused_optimizer.py",
    # the fused flash-attention backward (ISSUE 11) is every training
    # row's dominant backward kernel: its code must cold the training
    # caches so the rebuilt backward re-measures on the next TPU run
    "paddle_tpu/ops/pallas/flash_attention.py",
    # the fused residual+norm glue kernels and the prefetch/remat train
    # loop (ISSUE 19) sit inside every training row's step: glue-kernel
    # or fit-loop code changes must cold the training caches so the
    # rows re-measure with the current chain on the next TPU run
    "paddle_tpu/ops/pallas/fused_residual_norm.py",
    "paddle_tpu/hapi/model.py",
    "paddle_tpu/amp/__init__.py",
    "paddle_tpu/nn/functional/norm.py",
    # distributed tracing + fleet aggregation (ISSUE 12) ride the
    # training rows' hot paths (compile spans in every capture,
    # dispatch/collective spans, gpt_3d's skew/compile_ms columns):
    # their code must re-measure the rows it can perturb
    "paddle_tpu/observability/tracing.py",
    "paddle_tpu/observability/aggregate.py",
    # SLO guardrails, stall watchdog and the regression sentinel
    # (ISSUE 14): the watchdog arms Model.fit's step loop, the SLO
    # engine judges the serving rows, and the sentinel's verdict rides
    # every round's JSON tail — their code must cold the caches so
    # rows re-measure under the current guardrails on the next TPU run
    "paddle_tpu/observability/slo.py",
    "paddle_tpu/observability/watchdog.py",
    "paddle_tpu/observability/regress.py",
    # elastic training recovery (ISSUE 15): the collective watchdog
    # arms Group.psum_mean / apply_collective_grads / the pipeline
    # dispatches in every training row, and hybrid_bench's recovery
    # column measures the supervisor itself — rows re-measure when the
    # recovery machinery changes
    "paddle_tpu/resilience/elastic_train.py",
]


def _fn_version(*fns):
    import hashlib
    import inspect
    h = hashlib.sha256()
    for f in fns:
        h.update(inspect.getsource(f).encode())
    return h.hexdigest()[:16]


def _cached(dev, name, files, fn, src_fns=()):
    """Measured-evidence gate: load from benchmarks/measured/ when the
    producing code is unchanged, else measure now and persist. The key
    covers the shared timing harness, the per-entry measurement fns,
    and the bench-module constants their math depends on."""
    import hashlib
    kind = str(getattr(dev, "device_kind", dev.platform))
    consts = repr((_PEAK, WINDOW_STEPS))
    ver = mc.code_version(*_HARNESS_FILES, *files) \
        + _fn_version(_timed_window, _peak_flops, *src_fns) \
        + hashlib.sha256(consts.encode()).hexdigest()[:8]
    val = mc.load(kind, name, ver)
    if val is not None:
        return dict(val, cached=True)
    val = fn()
    mc.store(kind, name, ver, val)
    return val


def _timed_window(step, example, batches, repeats=2):
    """Compile a WindowRunner over ``batches``, then return the best-of-
    ``repeats`` wall seconds for one window (inputs pre-staged; timed
    region = one scan launch + one scalar loss readback)."""
    import paddle_tpu as paddle

    w = paddle.jit.WindowRunner(step, example, length=len(batches))
    t0 = time.perf_counter()
    stacks = w.stage(batches)
    stage_s = time.perf_counter() - t0
    float(w.run(*stacks, outputs="last"))  # compile the scanned window
    dt, last = float("inf"), 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        last = float(w.run(*stacks, outputs="last"))
        dt = min(dt, time.perf_counter() - t0)
    return dt, stage_s, w, last


def _calibration(cfg, batch, seq):
    """Measured kernel rates at THIS model's GEMM/attention shapes via the
    dispatch-free scan-slope method (benchmarks/calibrate.py), plus the
    matmul+attention roofline they imply. The evidence behind the mfu
    number: achieved model-TF/s must sit below the roofline."""
    import calibrate as cal

    tokens = batch * seq
    h = cfg.hidden_size
    gemm_ffn, _ = cal.measure_matmul(tokens, h, 4 * h, r1=16, r2=96)
    gemm_lm, dt_lm = cal.measure_matmul(tokens, h, cfg.vocab_size,
                                        r1=4, r2=24)
    att = cal.measure_attention(batch, cfg.num_heads, seq,
                                h // cfg.num_heads, r1=8, r2=48)
    # per-kernel fwd/bwd breakdown (ISSUE 11): the attention bwd/fwd
    # ratio regression — acceptance <= 3x vs the 4.5x the two-pass
    # backward measured — plus the norm/fused-optimizer kernels, in
    # every calibration row
    kernels = cal.kernel_breakdown(batch, seq, h, cfg.num_heads,
                                   cfg.num_layers, att=att)
    return {
        "gemm_ffn_tflops": round(gemm_ffn, 1),
        "gemm_lmhead_tflops": round(gemm_lm, 1),
        "attention_fwd_tflops": att["fwd"]["tflops"],
        "attention_fwd_ms": att["fwd"]["ms"],
        "attention_bwd_ms": att["bwd"]["ms"],
        "attention_bwd_fwd_ratio": kernels["attention_bwd_fwd_ratio"],
        "kernels": kernels,
        "method": "scan-slope, dispatch-free (benchmarks/calibrate.py)",
    }


def _bench_resnet50(peak):
    """North star #1 (BASELINE.json): ResNet50 images/sec/chip, AMP O2."""
    import gc

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.vision.models import resnet50

    # batch 32 / window 48: the true device step is ~13.6 ms (K-slope,
    # r5) but the ~71 ms fixed per-window launch cost dominated the old
    # K=6 number (25.3 "ms/step" was ~12 ms/step of launch cost). The
    # staged fp32 inputs at K=48 are ~925 MB and fit alongside the
    # activation peak; batch 64 exceeds HBM
    batch, iters = 32, 48
    paddle.seed(0)
    model = resnet50(num_classes=1000)
    model.train()
    opt = paddle.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
    model, opt = amp.decorate(models=model, optimizers=opt, level="O2",
                              dtype="bfloat16", master_weight=True)

    @paddle.jit.to_static
    def step(x, y):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)

    def batch_fn():
        x = rng.normal(size=(batch, 3, 224, 224)).astype(np.float32)
        y = rng.integers(0, 1000, (batch,)).astype(np.int64)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    for _ in range(2):
        loss = step(*batch_fn())
    float(loss)
    dt, _stage, w, _ = _timed_window(step, batch_fn(),
                                     [batch_fn() for _ in range(iters)])
    img_s = batch * iters / dt
    # ResNet50 fwd = 4.089e9 MACs/img = 8.18e9 FLOPs (2 per MAC, the
    # same convention as the GPT/BERT 6N rows); train = fwd + ~2x bwd
    achieved = img_s * 3 * 2 * 4.089e9
    del w, step, model, opt
    gc.collect()
    # conv roofline (scan-slope, both layouts, representative shapes):
    # the measured ceiling evidence for why images/sec sits where it does
    # (convs are ~6 ms of the step at b32 — the rest is BN/elementwise
    # HBM traffic; NHWC ~= NCHW, XLA already lays out for the MXU)
    import calibrate as cal
    roof = cal.calibrate_resnet50(batch=batch, shapes=(
        "conv1_7x7_s2", "s1_3x3", "s2_3x3", "s3_3x3", "s4_3x3",
        "s3_expand_1x1"))
    return {"metric": "resnet50_train_images_per_sec_per_chip",
            "value": round(img_s, 1), "unit": "images/sec",
            "batch": batch,
            "step_time_ms": round(dt / iters * 1e3, 2),
            "amp": "O2-bf16-master",
            "model_tflops_per_sec": round(achieved / 1e12, 2),
            "mfu": round(achieved / peak, 4),
            "conv_roofline": roof["roofline"]}


def _bench_bert(peak):
    """North star #2: BERT-base pretraining tokens/sec/chip (MLM+NSP).

    max_predictions=76 (the standard max_predictions_per_seq for seq 512
    at 15% masking): the MLM head gathers the masked positions before
    the vocab projection, so the [*, 30522] GEMM runs over ~15% of
    positions. MFU counts the vocab-head FLOPs only for the positions
    actually projected (honest accounting — see flops_method)."""
    import gc

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    # iters 32 (was 8): amortizes the ~71 ms fixed window-launch
    # cost to ~2 ms/step (see the r5 K-slope finding)
    batch, seq, iters, maxpred = 16, 512, 32, 76
    cfg = BertConfig(recompute=True,
                     recompute_policy="dots_and_kernels_saveable",
                     max_predictions=maxpred)
    paddle.seed(0)
    model = BertForPretraining(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    model, opt = amp.decorate(models=model, optimizers=opt, level="O2",
                              dtype="bfloat16", master_weight=True)

    @paddle.jit.to_static
    def step(ids, seg, mlm, nsp):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(ids, seg, mlm, nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)

    def batch_fn():
        ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        seg = np.zeros((batch, seq), np.int32)
        # <= maxpred masked positions per row (the reference pipeline's
        # max_predictions_per_seq contract)
        mlm = np.full((batch, seq), -100, np.int32)
        for b in range(batch):
            pos = rng.choice(seq, size=maxpred, replace=False)
            mlm[b, pos] = rng.integers(0, cfg.vocab_size, maxpred)
        nsp = rng.integers(0, 2, (batch,)).astype(np.int64)
        return tuple(paddle.to_tensor(v) for v in (ids, seg, mlm, nsp))

    for _ in range(2):
        loss = step(*batch_fn())
    float(loss)
    dt, _stage, w, _ = _timed_window(step, batch_fn(),
                                     [batch_fn() for _ in range(iters)])
    tok_s = batch * seq * iters / dt
    n = model.num_params()
    h, v = cfg.hidden_size, cfg.vocab_size
    # per-token model flops: 6*(N - vocab head) everywhere + the vocab
    # head only on the maxpred/seq fraction actually projected
    head = v * h
    flops_tok = (6.0 * (n - head) + 6.0 * head * (maxpred / seq)
                 + 12 * cfg.num_layers * h * seq)
    achieved = tok_s * flops_tok
    del w, step, model, opt
    gc.collect()
    return {"metric": "bert_base_pretrain_tokens_per_sec_per_chip",
            "value": round(tok_s, 1), "unit": "tokens/sec",
            "batch": batch, "seq_len": seq,
            "max_predictions": maxpred,
            "step_time_ms": round(dt / iters * 1e3, 2),
            "params": n, "amp": "O2-bf16-master",
            "model_tflops_per_sec": round(achieved / 1e12, 2),
            "mfu": round(achieved / peak, 4),
            "flops_method": ("6*(N - vocab_head) + 6*vocab_head*"
                             "(max_predictions/seq) + 12*L*H*S per token; "
                             "vocab-head flops counted only for projected "
                             "positions")}


def _bench_gpt_3d(peak):
    """Training-secondary row: hybrid DP x TP x PP GPT step over the
    fleet topology (benchmarks/hybrid_bench.py — tokens/sec on the full
    mesh, weak-scaling ratio vs 1 device, and the overlap scheduler's
    comm_ms / overlap_frac). Raises below 4 devices (single-chip rounds
    simply skip the row; the multichip driver picks it up)."""
    import jax

    import hybrid_bench
    if len(jax.devices()) < 4:
        raise RuntimeError("gpt_3d needs >= 4 devices")
    return hybrid_bench.bench_row(peak_flops=peak)


def _bench_optimizer():
    """Training-secondary row: fused vs per-param optimizer update at
    BERT-base and ResNet50 param sets (benchmarks/optimizer_bench.py —
    HLO update-op counts + eager update time + dispatch counts)."""
    import optimizer_bench
    return optimizer_bench.bench_row(small=False)


def main():
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"

    # whole-program audit bookkeeping (ISSUE 16): zero the per-code
    # finding counters so this round's record reports only programs
    # compiled by this bench process
    from paddle_tpu import analysis as _analysis
    _analysis.audit_counts(reset=True)

    if on_tpu:
        # dots_and_kernels_saveable: remat keeps matmul AND Pallas
        # (flash-attention) outputs, recomputing only elementwise ops —
        # measured 99.9 vs 104.2 ms/step over dots_saveable (the flash fwd
        # re-run in backward costs ~4 ms/step). batch 16 and recompute=False
        # both exceed HBM; XLA attention OOMs on the saved s^2 probs, so the
        # Pallas flash path is also the memory enabler
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024, dropout=0.0,
                        recompute=True,
                        recompute_policy="dots_and_kernels_saveable")
        batch, seq, warmup, iters = 8, 1024, 2, WINDOW_STEPS
    else:  # CPU smoke (local testing only; driver runs on the real chip)
        cfg = GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, dropout=0.0,
                        recompute=True)
        batch, seq, warmup, iters = 2, 64, 2, 4

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
    if on_tpu:
        # O2 (bf16 params + fp32 master weights) measured ~3% over O1:
        # per-op input casts disappear from the compiled step
        model, opt = amp.decorate(models=model, optimizers=opt,
                                  level="O2", dtype="bfloat16",
                                  master_weight=True)

    if on_tpu:
        # flash-attention block sizes for this model's shapes come from
        # the repo-persisted autotune cache (benchmarks/measured/); on a
        # cache miss this probe re-measures once (slope-timed,
        # validated) and persists the winner. The grad probe warms the
        # SEPARATE flash_attention_bwd entry (the fused backward tunes
        # its own blocks) so the train step never sweeps mid-window.
        import jax.numpy as jnp

        from paddle_tpu.incubate import autotune
        from paddle_tpu.ops.pallas import flash_attention as fa
        autotune.set_config({"kernel": {"enable": True}})
        probe = jnp.zeros((batch, seq, cfg.num_heads, cfg.head_dim),
                          jnp.bfloat16)
        import jax as _jax
        _jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(probe, probe, probe)

    level = "O2" if on_tpu else "O1"

    @paddle.jit.to_static
    def train_step(ids, labels):
        with amp.auto_cast(level=level, dtype="bfloat16"):
            loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)

    def batch_fn():
        ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        lab = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        return paddle.to_tensor(ids), paddle.to_tensor(lab)

    for _ in range(warmup):
        loss = train_step(*batch_fn())
    float(loss)  # sync

    # ONE dispatch per window of `iters` scanned steps, inputs pre-staged
    # on device (jit.WindowRunner): per-step host work — stack/slice
    # dispatches and the first-step launch — is hoisted out of the loop.
    # best of 3 windows: the host adds +-10% run-to-run scheduling
    # noise on top of stable device time (profiled)
    dt, stage_s, w, final_loss = _timed_window(
        train_step, batch_fn(), [batch_fn() for _ in range(iters)],
        repeats=3)
    stage_ms = stage_s * 1e3

    tokens_per_sec = batch * seq * iters / dt
    flops_per_token = model.flops_per_token(seq)
    achieved = tokens_per_sec * flops_per_token
    peak = _peak_flops(dev)
    mfu = achieved / peak if on_tpu else 0.0

    extra = {
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "batch": batch, "seq_len": seq, "iters": iters,
        "step_time_ms": round(dt / iters * 1e3, 2),
        "params": model.num_params(),
        "model_tflops_per_sec": round(achieved / 1e12, 2),
        "mfu": round(mfu, 4),
        "final_loss": round(final_loss, 4),
        "amp": "O2-bf16-master" if on_tpu else "O1-bf16", "recompute": True,
        "dispatch": "WindowRunner (1 dispatch / %d steps, inputs "
                    "pre-staged on device)" % iters,
        "host_overhead": {
            "stage_upload_ms_per_window": round(stage_ms, 1),
            "note": ("input staging happens once per window outside the "
                     "step loop; the timed region is one scan launch + "
                     "one scalar loss readback")},
        "flops_method": ("6*N_params + 12*L*H*S per token; backward "
                         "counted once, remat recompute NOT counted "
                         "(true-work MFU)"),
    }

    # static-vs-measured HBM accounting (ISSUE 16): the whole-program
    # audit's live-range sweep predicted a peak at compile time; compare
    # it against the measured captured-state residency while train_step
    # is still alive. ratio is the acceptance check (static within 25%
    # of measured program_state_bytes).
    try:
        from paddle_tpu import jit as _jit_mod
        static_b = _jit_mod._static_peak_bytes("train_step")
        measured_b = _jit_mod._program_state_bytes("train_step")
        if static_b and measured_b:
            extra["analysis_hbm"] = {
                "static_peak_bytes": int(static_b),
                "program_state_bytes": int(measured_b),
                "static_over_measured": round(static_b / measured_b, 3),
            }
    except Exception as e:  # accounting must never kill the bench
        print(f"analysis hbm accounting failed: {e}", file=sys.stderr)

    headline = {
        "metric": "gpt124m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": round(mfu / 0.40, 4),
    }

    def emit_enriched():
        print(json.dumps(dict(headline, extra=extra)), flush=True)

    def emit_compact():
        """The LAST stdout line, kept well under 500 bytes: the driver
        stores only the final 2000 BYTES of stdout and parses the last
        line, so the ~2.4KB enriched record must never be last (round-4
        postmortem: rc:0 but parsed:null — the line arrived beheaded).
        The enriched evidence is printed above AND persisted to
        benchmarks/measured/headline.json."""
        brief = {"device": extra["device"],
                 "step_time_ms": extra["step_time_ms"],
                 "mfu": extra["mfu"]}
        # the sentinel's verdict belongs in the tail the driver parses
        # (empty list = judged clean; absent = sentinel didn't run)
        if "regressions" in extra:
            brief["regressions"] = extra["regressions"][:4]
        for key, short in (("resnet50_train_images_per_sec_per_chip",
                            "resnet50"),
                           ("bert_base_pretrain_tokens_per_sec_per_chip",
                            "bert")):
            row = extra.get("secondary", {}).get(key)
            if row:
                brief[short] = {"value": row["value"], "unit": row["unit"],
                                "mfu": row["mfu"]}
        line = json.dumps(dict(headline, extra=brief))
        # never let the guard recreate the failure it prevents: drop
        # optional entries (newest first) until the line fits
        while len(line) > 500 and brief:
            brief.pop(next(reversed(brief)))
            line = json.dumps(dict(headline, extra=brief))
        if len(line) > 500:
            line = json.dumps(headline)
        print(line, flush=True)

    # kill-safety: the headline is measured — emit it NOW (compact, so
    # it parses even if the process dies mid-extras). The enriched
    # record below attaches calibration + north-star secondaries (cache
    # hits in benchmarks/measured/ unless their producing code changed),
    # then a compact line is re-emitted LAST.
    if on_tpu:
        emit_compact()
        import gc
        try:
            extra["calibration"] = _cached(
                dev, "calibration_gpt124m_b8s1024",
                ["benchmarks/calibrate.py",
                 "paddle_tpu/ops/pallas/flash_attention.py"],
                lambda: _calibration(cfg, batch, seq),
                src_fns=(_calibration,))
        except Exception as e:
            print(f"calibration failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        # free the GPT params/moments/compiled programs BEFORE the
        # secondary models — leaving them resident OOMs ResNet50/BERT
        del w, train_step, model, opt
        gc.collect()
        for name, files, fn, src in (
            ("secondary_resnet50",
             ["benchmarks/calibrate.py",
              "paddle_tpu/vision/models/resnet.py",
              "paddle_tpu/nn/functional/conv.py"],
             lambda: _bench_resnet50(peak), (_bench_resnet50,)),
            ("secondary_bert",
             ["paddle_tpu/models/bert.py",
              "paddle_tpu/ops/pallas/flash_attention.py",
              "paddle_tpu/distributed/fleet/recompute.py"],
             lambda: _bench_bert(peak), (_bench_bert,)),
            ("secondary_optimizer",
             ["benchmarks/optimizer_bench.py"],
             _bench_optimizer, (_bench_optimizer,)),
            ("secondary_gpt_3d",
             ["benchmarks/hybrid_bench.py",
              "paddle_tpu/distributed/fleet/pipeline.py",
              "paddle_tpu/distributed/fleet/topology.py",
              "paddle_tpu/distributed/overlap.py",
              "paddle_tpu/distributed/parallel.py",
              "paddle_tpu/core/meshutil.py"],
             lambda: _bench_gpt_3d(peak), (_bench_gpt_3d,)),
        ):
            try:
                row = _cached(dev, name, files, fn, src_fns=src)
                extra.setdefault("secondary", {})[row["metric"]] = {
                    k: v for k, v in row.items() if k != "metric"}
            except Exception as e:  # secondary must never kill the bench
                print(f"secondary bench failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            gc.collect()
        try:
            # serving rows are measured separately (benchmarks/
            # serving_bench.py, run on the chip outside the bench's
            # time budget) and embedded from the cache here
            import serving_bench
            srows = serving_bench.cached_rows(dev)
            if srows:
                extra["serving"] = {
                    k: {"ms_per_token": v["ms_per_token"],
                        "tokens_per_sec": v["tokens_per_sec"],
                        "kv_cache": v["kv_cache"], "batch": v["batch"]}
                    for k, v in srows.items() if "ms_per_token" in v}
                if "analysis" in srows:
                    extra.setdefault("analysis", {})[
                        "serving_findings"] = srows["analysis"]["findings"]
        except Exception as e:
            print(f"serving rows unavailable: {e}", file=sys.stderr)

    # per-code whole-program audit finding counts (ISSUE 16): the
    # sentinel judges them lower-is-better (regress.py special-cases
    # PDT* leaves), so a new warn-class finding in a compiled program
    # shows up as a regression against the checked-in history
    try:
        extra.setdefault("analysis", {})[
            "findings"] = _analysis.audit_counts()
    except Exception as e:
        print(f"audit counts unavailable: {e}", file=sys.stderr)

    # regression sentinel (ISSUE 14): judge THIS round against the
    # checked-in BENCH_r* history (median/MAD baselines; see
    # paddle_tpu/observability/regress.py) so the record self-reports
    # its own regressions in the JSON tail — the driver and the next
    # session see the dip without diffing history by hand.  TPU rounds
    # only: the history is TPU-measured, so judging a CPU smoke
    # against it would flag the hardware, not the code.
    if on_tpu:
        try:
            from paddle_tpu.observability import regress as _regress
            regs = _regress.check_record(dict(headline, extra=extra),
                                         _REPO)
            extra["regressions"] = regs
            if regs:
                print("regression sentinel: " + ", ".join(regs),
                      file=sys.stderr)
        except Exception as e:
            print(f"regression sentinel failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)

    # full evidence: to stdout (NOT last) and to a persisted file that
    # survives regardless of how the driver captures stdout
    emit_enriched()
    try:
        with open(os.path.join(_REPO, "benchmarks", "measured",
                               "headline.json"), "w") as f:
            json.dump(dict(headline, extra=extra), f, indent=1)
    except OSError as e:
        print(f"headline persist failed: {e}", file=sys.stderr)
    emit_compact()


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # still emit a parseable line on failure
        print(json.dumps({
            "metric": "gpt124m_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": "tokens/sec", "vs_baseline": 0.0,
            "error": f"{type(e).__name__}: {e}"}))
        sys.exit(1)
