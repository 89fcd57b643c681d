#!/usr/bin/env python
"""Attribute GPT-124M step time to components WITHOUT a device profiler.

Round 4's environment exported no xprof device events, so this
uses differential window timing: each variant changes exactly one
component of the training step; K-step scanned windows (one dispatch,
pre-staged inputs) give wall times whose DIFFERENCES isolate that
component's cost. Variants:

  full            the bench step (AdamW, CE loss, 12 layers, remat)
  sgd             AdamW -> plain SGD        => optimizer update cost
  mean_loss       CE -> logits.mean()       => CE + lm_head vjp cost
  no_head         loss on hidden states     => + lm_head GEMM cost
  layers_6        12 -> 6 layers            => per-layer encoder cost
  fwd_only        no backward/optimizer     => backward multiple
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _window_time(step, batch_fn, K=30, repeats=3):
    import paddle_tpu as paddle
    for _ in range(2):
        loss = step(*batch_fn())
    float(loss)
    w = paddle.jit.WindowRunner(step, batch_fn(), length=K)
    stacks = w.stage([batch_fn() for _ in range(K)])
    float(w.run(*stacks, outputs="last"))
    dt = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        float(w.run(*stacks, outputs="last"))
        dt = min(dt, time.perf_counter() - t0)
    return dt / K


def main():
    import gc

    import paddle_tpu as paddle
    import paddle_tpu.amp as amp
    from paddle_tpu.incubate import autotune
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    autotune.set_config({"kernel": {"enable": True}})
    batch, seq = 8, 1024
    results = {}

    def build(num_layers=12, opt_kind="adamw",
              policy="dots_and_kernels_saveable"):
        cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                        num_layers=num_layers, num_heads=12,
                        max_seq_len=1024, dropout=0.0, recompute=True,
                        recompute_policy=policy)
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.train()
        if opt_kind == "adamw":
            opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                         parameters=model.parameters())
        else:
            opt = paddle.optimizer.SGD(learning_rate=1e-4,
                                       parameters=model.parameters())
        model, opt = amp.decorate(models=model, optimizers=opt,
                                  level="O2", dtype="bfloat16",
                                  master_weight=True)
        return cfg, model, opt

    rng = np.random.default_rng(0)

    def batch_fn():
        ids = rng.integers(0, 50304, (batch, seq)).astype(np.int32)
        lab = rng.integers(0, 50304, (batch, seq)).astype(np.int32)
        return paddle.to_tensor(ids), paddle.to_tensor(lab)

    def run(name, step):
        ms = _window_time(step, batch_fn) * 1e3
        results[name] = round(ms, 2)
        print(f"{name}: {ms:.2f} ms/step", file=sys.stderr, flush=True)
        gc.collect()

    variants = sys.argv[1:] or ["full", "sgd", "mean_loss", "no_head",
                                "layers_6", "fwd_only"]

    if "full" in variants:
        cfg, model, opt = build()

        @paddle.jit.to_static
        def full(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("full", full)
        del model, opt, full

    if "sgd" in variants:
        cfg, model, opt = build(opt_kind="sgd")

        @paddle.jit.to_static
        def sgd_step(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("sgd", sgd_step)
        del model, opt, sgd_step

    if "mean_loss" in variants:
        cfg, model, opt = build()

        @paddle.jit.to_static
        def mean_loss(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                logits = model(ids)          # [B, S, V]
                loss = logits.astype("float32").mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("mean_loss", mean_loss)
        del model, opt, mean_loss

    if "no_head" in variants:
        cfg, model, opt = build()
        gpt_body = getattr(model, "gpt", None) or model._layers.gpt

        @paddle.jit.to_static
        def no_head(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                h = gpt_body(ids)            # hidden states only
                loss = h.astype("float32").mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("no_head", no_head)
        del model, opt, no_head, gpt_body

    if "layers_6" in variants:
        cfg, model, opt = build(num_layers=6)

        @paddle.jit.to_static
        def six(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("layers_6", six)
        del model, opt, six

    if "fwd_only" in variants:
        cfg, model, opt = build()
        model.eval()

        @paddle.jit.to_static
        def fwd(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = model(ids, labels)
            return loss
        run("fwd_only", fwd)
        del model, opt, fwd

    if "relu" in variants:
        # gelu(tanh) -> relu in the MLP: isolates the transcendental
        # (VPU) cost of gelu fwd + bwd + remat recompute
        from paddle_tpu.models import gpt as gpt_mod
        import paddle_tpu.nn.functional as F
        orig_fwd = gpt_mod.GPTMLP.forward
        gpt_mod.GPTMLP.forward = \
            lambda self, x: self.fc2(F.relu(self.fc1(x)))
        try:
            cfg, model, opt = build()

            @paddle.jit.to_static
            def relu_step(ids, labels):
                with amp.auto_cast(level="O2", dtype="bfloat16"):
                    loss = model(ids, labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss
            run("relu", relu_step)
            del model, opt, relu_step
        finally:
            gpt_mod.GPTMLP.forward = orig_fwd

    if "xla_ln" in variants:
        # LayerNorm via jnp instead of the Pallas kernel: the custom
        # call is a fusion barrier; XLA may fuse the jnp form into the
        # surrounding residual-add/cast chains and win in-context
        import os
        os.environ["PDTPU_NORM_BACKEND"] = "xla"
        try:
            cfg, model, opt = build()

            @paddle.jit.to_static
            def xla_ln_step(ids, labels):
                with amp.auto_cast(level="O2", dtype="bfloat16"):
                    loss = model(ids, labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss
            run("xla_ln", xla_ln_step)
            del model, opt, xla_ln_step
        finally:
            os.environ.pop("PDTPU_NORM_BACKEND", None)

    if "save_names" in variants:
        # transformer_saveable: ln/gelu outputs saved across backward
        cfg, model, opt = build(policy="transformer_saveable")

        @paddle.jit.to_static
        def save_names_step(ids, labels):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss
        run("save_names", save_names_step)
        del model, opt, save_names_step

    if "ln_off" in variants:
        # LayerNorm -> identity: upper bound on ALL norm-related cost
        from paddle_tpu.nn import layers as nl
        orig_ln = nl.LayerNorm.forward
        nl.LayerNorm.forward = lambda self, x: x
        try:
            cfg, model, opt = build()

            @paddle.jit.to_static
            def ln_off_step(ids, labels):
                with amp.auto_cast(level="O2", dtype="bfloat16"):
                    loss = model(ids, labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss
            run("ln_off", ln_off_step)
            del model, opt, ln_off_step
        finally:
            nl.LayerNorm.forward = orig_ln

    # ----------------------------------------------------- ResNet50 --
    # VERDICT r5 item 2: conv is only ~5 ms of the 25 ms step (the r4
    # calibration refuted the MXU-underfill excuse) — locate the other
    # ~20 ms: BN? optimizer? data movement?
    def build_resnet(opt_kind="momentum"):
        from paddle_tpu.vision.models import resnet50
        paddle.seed(0)
        model = resnet50(num_classes=1000)
        model.train()
        if opt_kind == "momentum":
            opt = paddle.optimizer.Momentum(
                learning_rate=0.1, momentum=0.9,
                parameters=model.parameters())
        else:
            opt = None
        if opt is not None:
            model, opt = amp.decorate(models=model, optimizers=opt,
                                      level="O2", dtype="bfloat16",
                                      master_weight=True)
        else:
            model = amp.decorate(models=model, level="O2",
                                 dtype="bfloat16")
        return model, opt

    rbatch = 32

    def rbatch_fn():
        x = rng.normal(size=(rbatch, 3, 224, 224)).astype(np.float32)
        y = rng.integers(0, 1000, (rbatch,)).astype(np.int64)
        return paddle.to_tensor(x), paddle.to_tensor(y)

    def resnet_step(model, opt):
        @paddle.jit.to_static
        def step(x, y):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = paddle.nn.functional.cross_entropy(model(x), y)
            loss.backward()
            if opt is not None:
                opt.step()
                opt.clear_grad()
            return loss
        return step

    def run_resnet(name, model, opt):
        step = resnet_step(model, opt)
        ms = _window_time(step, rbatch_fn, K=6) * 1e3
        results[name] = round(ms, 2)
        print(f"{name}: {ms:.2f} ms/step", file=sys.stderr, flush=True)
        gc.collect()

    if "resnet_full" in variants:
        model, opt = build_resnet()
        run_resnet("resnet_full", model, opt)
        del model, opt

    if "resnet_bn_off" in variants:
        from paddle_tpu.nn import layers as nl
        orig_bn = nl.BatchNorm2D.forward
        nl.BatchNorm2D.forward = lambda self, x: x
        try:
            model, opt = build_resnet()
            run_resnet("resnet_bn_off", model, opt)
            del model, opt
        finally:
            nl.BatchNorm2D.forward = orig_bn

    if "resnet_opt_off" in variants:
        model, opt = build_resnet(opt_kind="none")
        run_resnet("resnet_opt_off", model, opt)
        del model, opt

    if "resnet_fwd_only" in variants:
        model, _ = build_resnet(opt_kind="none")
        model.eval()

        @paddle.jit.to_static
        def fwd(x, y):
            with amp.auto_cast(level="O2", dtype="bfloat16"):
                loss = paddle.nn.functional.cross_entropy(model(x), y)
            return loss
        ms = _window_time(fwd, rbatch_fn, K=6) * 1e3
        results["resnet_fwd_only"] = round(ms, 2)
        print(f"resnet_fwd_only: {ms:.2f} ms/step", file=sys.stderr,
              flush=True)
        del model, fwd
        gc.collect()

    # derived attributions
    d = {}
    if "resnet_full" in results and "resnet_bn_off" in results:
        d["resnet_bn_ms"] = round(
            results["resnet_full"] - results["resnet_bn_off"], 2)
    if "resnet_full" in results and "resnet_opt_off" in results:
        d["resnet_momentum_ms"] = round(
            results["resnet_full"] - results["resnet_opt_off"], 2)
    if "resnet_full" in results and "resnet_fwd_only" in results:
        d["resnet_bwd_plus_opt_ms"] = round(
            results["resnet_full"] - results["resnet_fwd_only"], 2)
    if "full" in results and "sgd" in results:
        d["adamw_minus_sgd_ms"] = round(results["full"] - results["sgd"], 2)
    if "full" in results and "mean_loss" in results:
        d["ce_loss_ms"] = round(results["full"] - results["mean_loss"], 2)
    if "mean_loss" in results and "no_head" in results:
        d["lm_head_gemms_ms"] = round(
            results["mean_loss"] - results["no_head"], 2)
    if "full" in results and "layers_6" in results:
        d["per_layer_ms"] = round(
            (results["full"] - results["layers_6"]) / 6.0, 2)
    if "full" in results and "fwd_only" in results:
        d["bwd_plus_opt_ms"] = round(
            results["full"] - results["fwd_only"], 2)
    if "full" in results and "relu" in results:
        d["gelu_minus_relu_ms"] = round(
            results["full"] - results["relu"], 2)
    if "full" in results and "xla_ln" in results:
        d["pallas_ln_minus_xla_ln_ms"] = round(
            results["full"] - results["xla_ln"], 2)
    if "full" in results and "ln_off" in results:
        d["ln_total_ms"] = round(results["full"] - results["ln_off"], 2)
    print(json.dumps({"variants_ms": results, "derived": d}, indent=1))


if __name__ == "__main__":
    main()
