#!/usr/bin/env python
"""On-chip calibration: measured bf16 matmul TF/s at GPT-124M's actual GEMM
shapes, attention fwd/bwd TF/s, and a matmul-only roofline for the bench
config. Emits one JSON object (and writes it to argv[1] if given).

Methodology — a dispatch carries milliseconds of fixed host latency, and
in rounds 2-5 ``block_until_ready`` returned before the device was done
(measured: it "timed" an 8192^3 matmul at 57 PF/s), so naive per-call
timing is garbage at these op sizes. Instead each op runs R times *inside one compiled
program* (lax.scan over R distinct stacked inputs, accumulating into the
output so nothing can be elided or hoisted), timed at two values of R with
host-readback sync; the slope (t_R2 - t_R1) / (R2 - R1) is pure kernel
time, with dispatch overhead and sync cost cancelled.

The roofline is matmul+attention kernel time only (elementwise, softmax,
optimizer, dispatch all ride free in its idealised world), so real step
time must exceed it; the ratio is the schedulable headroom.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _sync(x):
    """True device sync: host readback of a scalar (block_until_ready
    returned early in rounds 2-5 — see module docstring)."""
    return float(jnp.asarray(x).reshape(-1)[0].astype(jnp.float32))


def _time_call(fn, *args, iters=4, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


def _scanned_matmul(m, k, n, reps, dtype=jnp.bfloat16, seed=0):
    """One jit program running ``reps`` sequential [m,k]@[k,n] matmuls.
    One operand is perturbed by the (traced) iteration index so XLA cannot
    CSE or hoist the dot. The perturbing add rides in the slope (it does
    NOT cancel), so it goes on the SMALLER operand — its elementwise cost
    is then 1-3% of the GEMM at these shapes, the stated accuracy of this
    calibration."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)) * 0.1, dtype)
    b = jnp.asarray(rng.normal(size=(k, n)) * 0.1, dtype)
    perturb_a = m * k <= k * n

    @jax.jit
    def f(a, b):
        def body(c, i):
            eps = i.astype(dtype) * 1e-6
            if perturb_a:
                return c + (a + eps) @ b, None
            return c + a @ (b + eps), None
        return jax.lax.scan(body, jnp.zeros((m, n), dtype),
                            jnp.arange(reps))[0]

    return f, (a, b)


def measure_matmul(m, k, n, r1=32, r2=256):
    """Kernel-only TF/s via the two-R slope (fixed dispatch+sync overhead
    cancels; large r2-r1 swamps the per-call dispatch jitter)."""
    f1, a1 = _scanned_matmul(m, k, n, r1)
    f2, a2 = _scanned_matmul(m, k, n, r2)
    t1 = _time_call(f1, *a1)
    t2 = _time_call(f2, *a2)
    per_op = max((t2 - t1) / (r2 - r1), 1e-9)
    return 2.0 * m * k * n / per_op / 1e12, per_op


def _scanned_attention(batch, heads, seq, head_dim, reps, causal, bwd):
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.default_rng(0)
    shp = (batch, seq, heads, head_dim)
    q = jnp.asarray(rng.normal(size=shp) * 0.1, jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shp) * 0.1, jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shp) * 0.1, jnp.bfloat16)

    def one(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal)

    if not bwd:
        @jax.jit
        def f(q, k, v):
            def body(c, i):
                return c + one(q + i.astype(q.dtype) * 1e-6, k, v), None
            z = jnp.zeros(shp, jnp.bfloat16)
            return jax.lax.scan(body, z, jnp.arange(reps))[0]
    else:
        grad = jax.grad(
            lambda q, k, v: one(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))

        @jax.jit
        def f(q, k, v):
            def body(c, i):
                # all three grads feed the carry so the dkv kernel cannot
                # be dead-code-eliminated from the timed program
                dq, dk, dv = grad(q + i.astype(q.dtype) * 1e-6, k, v)
                return c + (dq + dk + dv).astype(jnp.bfloat16), None
            z = jnp.zeros(shp, jnp.bfloat16)
            return jax.lax.scan(body, z, jnp.arange(reps))[0]

    return f, (q, k, v)


def measure_attention(batch, heads, seq, head_dim, causal=True,
                      r1=8, r2=48):
    res = {}
    for tag, bwd in (("fwd", False), ("bwd", True)):
        f1, a1 = _scanned_attention(batch, heads, seq, head_dim, r1,
                                    causal, bwd)
        f2, a2 = _scanned_attention(batch, heads, seq, head_dim, r2,
                                    causal, bwd)
        t1 = _time_call(f1, *a1)
        t2 = _time_call(f2, *a2)
        per_op = max((t2 - t1) / (r2 - r1), 1e-9)
        flops = 4.0 * batch * heads * seq * seq * head_dim
        if causal:
            flops *= 0.5
        if bwd:
            flops *= 2.5  # dQ,dK,dV + recompute
        res[tag] = {"tflops": round(flops / per_op / 1e12, 2),
                    "ms": round(per_op * 1e3, 3)}
    return res


def _scanned_norm(rows, hidden, reps, bwd):
    """One jit program running ``reps`` Pallas layer_norms (optionally
    + input/weight/bias grads), index-perturbed like the matmul scan."""
    from paddle_tpu.ops.pallas import norms

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(rows, hidden)) * 0.1, jnp.float32)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    def one(x, w, b):
        return norms.layer_norm(x, w, b)

    if not bwd:
        @jax.jit
        def f(x, w, b):
            def body(c, i):
                return c + one(x + i.astype(x.dtype) * 1e-6, w, b), None
            return jax.lax.scan(body, jnp.zeros_like(x),
                                jnp.arange(reps))[0]
    else:
        grad = jax.grad(lambda x, w, b: one(x, w, b).sum(),
                        argnums=(0, 1, 2))

        @jax.jit
        def f(x, w, b):
            def body(c, i):
                dx, dw, db = grad(x + i.astype(x.dtype) * 1e-6, w, b)
                return c + dx + (dw.sum() + db.sum()), None
            return jax.lax.scan(body, jnp.zeros_like(x),
                                jnp.arange(reps))[0]

    return f, (x, w, b)


def measure_norm(rows, hidden, r1=16, r2=96):
    res = {}
    for tag, bwd in (("fwd", False), ("bwd", True)):
        f1, a1 = _scanned_norm(rows, hidden, r1, bwd)
        f2, a2 = _scanned_norm(rows, hidden, r2, bwd)
        per_op = max((_time_call(f2, *a2) - _time_call(f1, *a1))
                     / (r2 - r1), 1e-9)
        res[tag] = {"ms": round(per_op * 1e3, 4)}
    return res


def _scanned_fused_opt(n, reps):
    """One jit program running ``reps`` fused AdamW bucket updates on an
    ``n``-element f32 flat (the PR4 one-kernel-per-bucket path), state
    threaded through the scan carry so nothing is elided."""
    from paddle_tpu.ops.pallas import fused_optimizer as fo

    spec = fo.UpdateSpec(kind="adamw", beta1=0.9, beta2=0.999,
                         eps=1e-8, decay=0.01)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n,)) * 0.1, jnp.float32)
    g = jnp.asarray(rng.normal(size=(n,)) * 0.01, jnp.float32)
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)

    @jax.jit
    def f(w, g, m, v):
        def body(carry, i):
            w, m, v, b1p, b2p = carry
            nw, _, nm, nv, nb1, nb2 = fo.fused_update(
                spec, w=w, g=g + i.astype(g.dtype) * 1e-9, lr=1e-3,
                m=m, v=v, b1p=b1p, b2p=b2p)
            return (nw, nm, nv, nb1, nb2), None
        init = (w, m, v, jnp.float32(0.9), jnp.float32(0.999))
        return jax.lax.scan(body, init, jnp.arange(reps))[0][0]

    return f, (w, g, m, v)


def measure_fused_optimizer(n, r1=8, r2=48):
    f1, a1 = _scanned_fused_opt(n, r1)
    f2, a2 = _scanned_fused_opt(n, r2)
    per_op = max((_time_call(f2, *a2) - _time_call(f1, *a1))
                 / (r2 - r1), 1e-9)
    return {"ms": round(per_op * 1e3, 4), "elements": n}


def _scanned_glue(rows, hidden, reps, bwd, fused):
    """One jit program running ``reps`` residual-add+layer-norm glue
    chains (optionally + input/weight/bias grads), index-perturbed like
    the matmul scan. ``fused`` picks the ISSUE-19 single-dispatch
    kernel; unfused is the dispatch chain the training blocks emit
    today (add, then the Pallas layer_norm). Both consume the residual
    AND the normed output so neither branch can be elided."""
    from paddle_tpu.ops.pallas import fused_residual_norm as frn
    from paddle_tpu.ops.pallas import norms

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(rows, hidden)) * 0.1, jnp.float32)
    y = jnp.asarray(rng.normal(size=(rows, hidden)) * 0.1, jnp.float32)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    if fused:
        def one(x, y, w, b):
            res, o = frn.fused_residual_layer_norm(x, y, w, b)
            return res + o
    else:
        def one(x, y, w, b):
            res = x + y
            return res + norms.layer_norm(res, w, b)

    if not bwd:
        @jax.jit
        def f(x, y, w, b):
            def body(c, i):
                return c + one(x + i.astype(x.dtype) * 1e-6, y, w, b), None
            return jax.lax.scan(body, jnp.zeros_like(x),
                                jnp.arange(reps))[0]
    else:
        grad = jax.grad(lambda x, y, w, b: one(x, y, w, b).sum(),
                        argnums=(0, 1, 2, 3))

        @jax.jit
        def f(x, y, w, b):
            def body(c, i):
                dx, dy, dw, db = grad(x + i.astype(x.dtype) * 1e-6,
                                      y, w, b)
                return c + dx + dy + (dw.sum() + db.sum()), None
            return jax.lax.scan(body, jnp.zeros_like(x),
                                jnp.arange(reps))[0]

    return f, (x, y, w, b)


def measure_glue(rows, hidden, r1=16, r2=96):
    """Fused vs unfused training-glue kernel ms (fwd and bwd) via the
    two-R slope."""
    res = {}
    for kind, fused in (("fused", True), ("unfused", False)):
        res[kind] = {}
        for tag, bwd in (("fwd", False), ("bwd", True)):
            f1, a1 = _scanned_glue(rows, hidden, r1, bwd, fused)
            f2, a2 = _scanned_glue(rows, hidden, r2, bwd, fused)
            per_op = max((_time_call(f2, *a2) - _time_call(f1, *a1))
                         / (r2 - r1), 1e-9)
            res[kind][tag] = {"ms": round(per_op * 1e3, 4)}
    return res


def measure_train_glue_dispatches(hidden=32, heads=4, vocab=96, seq=16,
                                  batch=2):
    """Per-layer TRAINING-forward dispatch count of the GPT block
    chain, glue fusion off vs on (ISSUE 19) — counted exactly by the
    profiler op-hook at L=1 and L=2 tiny-GPT configs; the
    difference isolates the per-layer chain from embedding/final-norm
    constants. Forward-only by construction: the backward replays
    inside ``jax.vjp`` and never re-enters the dispatcher, so its cost
    shows up in the ``measure_glue`` scan-slope ms, not here. The
    ``glue_*`` counts are the norm/residual subset (add, layer_norm,
    rms_norm, fused_residual_norm) of the totals."""
    import paddle_tpu as pp
    from paddle_tpu.core import dispatch as _dispatch
    from paddle_tpu.core import state as _state
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.gpt import GPTConfig, GPTModel

    GLUE_OPS = ("add", "layer_norm", "rms_norm", "fused_residual_norm")

    def count_ops(layers, fused):
        pp.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=seq, dropout=0.0,
                        use_flash_attention=False)
        model = GPTModel(cfg)
        model.train()
        ids = Tensor(np.zeros((batch, seq), np.int32))
        n, g = [0], [0]

        def hook(name, t0, t1):
            n[0] += 1
            if name in GLUE_OPS:
                g[0] += 1

        # flag hygiene: entry flag restored on ANY exit (the PR4
        # setup-inside-the-try rule) — a crashed count must not leave
        # glue fusion flipped for the rest of the process
        old = _state.get_flag("train_glue_fusion")
        _dispatch._profile_hook = hook
        try:
            _state.set_flags({"train_glue_fusion": fused})
            with pp.no_grad():
                model(ids)
        finally:
            _dispatch._profile_hook = None
            _state.set_flags({"train_glue_fusion": old})
        return n[0], g[0]

    u1, gu1 = count_ops(1, False)
    u2, gu2 = count_ops(2, False)
    f1, gf1 = count_ops(1, True)
    f2, gf2 = count_ops(2, True)
    out = {
        "method": "op-hook dispatch count of one eager TRAIN forward "
                  "(L=2 minus L=1 isolates the per-layer chain; "
                  "backward runs inside jax.vjp, not counted)",
        "unfused_per_layer": u2 - u1,
        "fused_per_layer": f2 - f1,
        "glue_unfused_per_layer": gu2 - gu1,
        "glue_fused_per_layer": gf2 - gf1,
    }
    _log(f"train glue dispatches/layer: {out['unfused_per_layer']} -> "
         f"{out['fused_per_layer']} (glue subset "
         f"{out['glue_unfused_per_layer']} -> "
         f"{out['glue_fused_per_layer']})")
    return out


def measure_remat_fraction(hidden=32, heads=4, vocab=96, seq=16,
                           batch=2, layers=2,
                           policy="dots_and_kernels_saveable"):
    """Recompute fraction of selective remat, as an exact program-size
    count: flattened jaxpr eqns of the captured train step with remat
    on minus off, over the forward-only eqn count — 'what share of the
    forward does the backward replay'. Uses the analyzer's
    ``jaxpr_eqn_count`` stamp (``analysis.flat_eqn_count`` recursing
    into remat sub-jaxprs), so it needs PDTPU_ANALYSIS != off; returns
    None fractions when the stamp is unavailable."""
    import paddle_tpu as pp
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    def eqns(remat, fwd_only=False):
        pp.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=seq, dropout=0.0,
                        use_flash_attention=False)
        m = GPTForCausalLM(cfg)
        if remat:
            for blk in m.gpt.blocks:
                blk._recompute = True
                blk._recompute_policy = policy
        m.train()
        opt = pp.optimizer.SGD(learning_rate=0.01,
                               parameters=m.parameters())

        if fwd_only:
            @pp.jit.to_static(full_graph=True)
            def step(ids, labels):
                return m(ids, labels)
        else:
            @pp.jit.to_static(full_graph=True)
            def step(ids, labels):
                loss = m(ids, labels)
                loss.backward()
                opt.step()
                opt.clear_grad()
                return loss
        ids = pp.to_tensor(np.zeros((batch, seq), np.int32))
        step(ids, ids)
        exe = next(iter(step._cache.values()))
        return int(getattr(exe, "jaxpr_eqn_count", 0) or 0)

    fwd = eqns(False, fwd_only=True)
    off = eqns(False)
    on = eqns(True)
    frac = round((on - off) / fwd, 3) if fwd and off and on else None
    out = {
        "method": "flattened jaxpr eqn count of the captured train "
                  "step (analysis.flat_eqn_count), remat on minus off "
                  "over the forward-only count",
        "policy": policy,
        "fwd_eqns": fwd,
        "step_eqns": off,
        "step_eqns_remat": on,
        "recompute_fraction": frac,
    }
    _log(f"remat recompute fraction [{policy}]: {frac} "
         f"(fwd {fwd} eqns, step {off} -> {on})")
    return out


def train_batch_headroom(budget_gb=16.0, hidden=768, layers=4, heads=12,
                         vocab=1024, seq=256, batches=(1, 2, 4, 8, 16),
                         remat=None):
    """Walk doubling batch sizes against the PR16 static-peak gauge:
    capture the full train step (fwd+bwd+optimizer) at each batch size
    and read the analyzer's ``static_peak_bytes`` off the executable —
    the same number the ``hbm.static_peak_bytes{fn}`` gauge exports.
    ``remat`` (a fleet.recompute policy name) prices the selective-
    remat headroom: the largest batch whose static peak fits the
    budget is the train-batch headroom of the config. A CAPTURE-only
    walk — nothing trains; rows after the first over-budget batch are
    skipped (the peak is monotone in batch)."""
    import paddle_tpu as pp
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    budget = int(budget_gb * (1 << 30))
    rows, max_fit = [], None
    for bs in batches:
        pp.seed(0)
        cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                        num_layers=layers, num_heads=heads,
                        max_seq_len=seq, dropout=0.0,
                        use_flash_attention=False)
        m = GPTForCausalLM(cfg)
        if remat:
            for blk in m.gpt.blocks:
                blk._recompute = True
                blk._recompute_policy = remat
        m.train()
        opt = pp.optimizer.SGD(learning_rate=0.01,
                               parameters=m.parameters())

        @pp.jit.to_static(full_graph=True)
        def step(ids, labels):
            loss = m(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids = pp.to_tensor(np.zeros((bs, seq), np.int32))
        step(ids, ids)
        exe = next(iter(step._cache.values()))
        peak = int(getattr(exe, "static_peak_bytes", 0) or 0)
        fits = bool(peak and peak <= budget)
        rows.append({"batch": bs, "static_peak_bytes": peak,
                     "fits": fits})
        _log(f"headroom: batch {bs} static peak "
             f"{peak / (1 << 20):.0f} MiB "
             f"({'fits' if fits else 'OVER'} {budget_gb} GiB)"
             + (f" [remat={remat}]" if remat else ""))
        if fits:
            max_fit = bs
        elif peak:
            break  # monotone: larger batches only get worse
    return {"budget_bytes": budget, "remat": remat,
            "max_batch_fits": max_fit, "rows": rows}


def kernel_breakdown(batch=8, seq=1024, hidden=768, heads=12, layers=12,
                     n_params=None, att=None):
    """Per-kernel fwd/bwd breakdown at the bench GPT-124M shapes —
    emitted with EVERY calibration run so the attention backward/forward
    ratio (the ISSUE-11 regression: 4.5x measured vs ~2.5x FLOP-ideal)
    is tracked as a number, alongside the norm and fused-optimizer
    kernels that ride the same step. ``att``: reuse an already-measured
    ``measure_attention`` result instead of re-sweeping. ``n_params``:
    the fused-optimizer bucket size; defaults to the calibrated model's
    transformer-block parameter count (12*L*H^2, the dominant flat
    bucket) so a tiny-config calibration times a tiny bucket instead of
    a hardcoded GPT-124M one."""
    if n_params is None:
        n_params = 12 * layers * hidden * hidden
    n_params = max(1024, -(-int(n_params) // 1024) * 1024)  # ALIGN pad
    if att is None:
        att = measure_attention(batch, heads, seq, hidden // heads)
    ratio = (att["bwd"]["ms"] / att["fwd"]["ms"]
             if att["fwd"]["ms"] else None)
    out = {
        "attention": {"fwd_ms": att["fwd"]["ms"],
                      "bwd_ms": att["bwd"]["ms"],
                      "fwd_tflops": att["fwd"]["tflops"],
                      "bwd_tflops": att["bwd"]["tflops"],
                      "per_layer": True},
        "attention_bwd_fwd_ratio": round(ratio, 2) if ratio else None,
        "attention_bwd_fwd_ratio_flop_ideal": 2.5,
        "layernorm": dict(measure_norm(batch * seq, hidden),
                          shape=[batch * seq, hidden]),
        "fused_optimizer": measure_fused_optimizer(n_params),
        # training glue share (ISSUE 19): norm/residual dispatch count
        # per TRAIN layer (fused vs unfused) plus the fused-vs-unfused
        # glue chain ms, fwd and bwd — the per-step glue budget the
        # train_glue_fusion flag buys back
        "glue": dict(measure_train_glue_dispatches(),
                     **{"chain": dict(measure_glue(batch * seq, hidden),
                                      shape=[batch * seq, hidden])}),
        # selective-remat recompute share (ISSUE 19): exact program-
        # size fraction the backward replays under the default policy
        "remat": measure_remat_fraction(),
    }
    glue_ms = out["glue"]["chain"]
    _log(f"kernels: attn fwd {att['fwd']['ms']} ms / bwd "
         f"{att['bwd']['ms']} ms (ratio {out['attention_bwd_fwd_ratio']}"
         f"), ln fwd {out['layernorm']['fwd']['ms']} / bwd "
         f"{out['layernorm']['bwd']['ms']} ms, fused-opt "
         f"{out['fused_optimizer']['ms']} ms")
    _log(f"glue chain: fused fwd {glue_ms['fused']['fwd']['ms']} / bwd "
         f"{glue_ms['fused']['bwd']['ms']} ms vs unfused fwd "
         f"{glue_ms['unfused']['fwd']['ms']} / bwd "
         f"{glue_ms['unfused']['bwd']['ms']} ms; "
         f"remat recompute fraction "
         f"{out['remat']['recompute_fraction']}")
    return out


def _scanned_conv(n, h, w, cin, cout, kh, kw, stride, reps, fmt="NCHW",
                  bwd=False, dtype=jnp.bfloat16):
    """One jit program running ``reps`` convs (optionally + input/weight
    grads), index-perturbed like the matmul scan."""
    rng = np.random.default_rng(0)
    xshape = (n, cin, h, w) if fmt == "NCHW" else (n, h, w, cin)
    x = jnp.asarray(rng.normal(size=xshape) * 0.1, dtype)
    wgt = jnp.asarray(rng.normal(size=(cout, cin, kh, kw)) * 0.1, dtype)
    dn = jax.lax.conv_dimension_numbers(
        xshape, wgt.shape,
        (fmt, "OIHW", fmt))
    pad = ((kh // 2, kh // 2), (kw // 2, kw // 2))

    def conv(x, wgt):
        return jax.lax.conv_general_dilated(
            x, wgt, (stride, stride), pad, dimension_numbers=dn)

    if not bwd:
        @jax.jit
        def f(x, wgt):
            def body(c, i):
                return c + conv(x + i.astype(dtype) * 1e-6, wgt), None
            z = jnp.zeros(jax.eval_shape(conv, x, wgt).shape, dtype)
            return jax.lax.scan(body, z, jnp.arange(reps))[0]
    else:
        grad = jax.grad(
            lambda x, wgt: conv(x, wgt).astype(jnp.float32).sum(),
            argnums=(0, 1))

        @jax.jit
        def f(x, wgt):
            def body(c, i):
                # BOTH grads must feed the carry: dropping dw would let
                # XLA dead-code-eliminate the dW convolution from the
                # timed program (and conv is linear, so the forward never
                # runs in the grad program — bwd times exactly dX+dW)
                dx, dw = grad(x + i.astype(dtype) * 1e-6, wgt)
                return c + dx.astype(dtype) + dw.sum().astype(dtype), None
            return jax.lax.scan(body, jnp.zeros(xshape, dtype),
                                jnp.arange(reps))[0]

    return f, (x, wgt)


def measure_conv(n, h, w, cin, cout, kh, kw, stride=1, fmt="NCHW",
                 bwd=False, r1=None, r2=None):
    """Kernel-only conv TF/s via the two-R slope. ResNet-class convs run
    in tens of microseconds, far below the per-dispatch jitter —
    the default rep counts auto-scale so that r2-r1 puts >= ~25 kernel-
    milliseconds between the two timed programs (estimated at 100 TF/s).
    A slope that still comes out non-positive is below timing resolution:
    the returned TF/s is None in that case, never a fabricated number."""
    ho, wo = h // stride, w // stride
    flops = 2.0 * n * ho * wo * cout * cin * kh * kw
    if bwd:
        flops *= 2.0  # dX + dW (the fwd conv is linear: not in the program)
    if r1 is None or r2 is None:
        est = flops / 100e12  # optimistic per-rep seconds
        delta = max(32, int(0.025 / max(est, 1e-7)))
        delta = min(delta, 2048)
        r1, r2 = max(4, delta // 8), max(4, delta // 8) + delta
    f1, a1 = _scanned_conv(n, h, w, cin, cout, kh, kw, stride, r1, fmt, bwd)
    f2, a2 = _scanned_conv(n, h, w, cin, cout, kh, kw, stride, r2, fmt, bwd)
    t1 = _time_call(f1, *a1)
    t2 = _time_call(f2, *a2)
    per_op = (t2 - t1) / (r2 - r1)
    if per_op <= 0:
        return None, None
    return flops / per_op / 1e12, per_op


# ResNet50 bottleneck conv inventory: (h, w, cin, cout, k, stride, count)
# per forward pass (conv1 + 4 stages; downsample convs folded into count-
# weighted equivalents; fc excluded — it is a tiny matmul).
_RESNET50_CONVS = [
    ("conv1_7x7_s2", 224, 224, 3, 64, 7, 2, 1),
    ("s1_reduce_1x1", 56, 56, 256, 64, 1, 1, 2),     # +first from 64
    ("s1_3x3", 56, 56, 64, 64, 3, 1, 3),
    ("s1_expand_1x1", 56, 56, 64, 256, 1, 1, 3),
    ("s2_reduce_1x1", 28, 28, 512, 128, 1, 1, 3),
    ("s2_3x3", 28, 28, 128, 128, 3, 1, 4),
    ("s2_expand_1x1", 28, 28, 128, 512, 1, 1, 4),
    ("s3_reduce_1x1", 14, 14, 1024, 256, 1, 1, 5),
    ("s3_3x3", 14, 14, 256, 256, 3, 1, 6),
    ("s3_expand_1x1", 14, 14, 256, 1024, 1, 1, 6),
    ("s4_reduce_1x1", 7, 7, 2048, 512, 1, 1, 2),
    ("s4_3x3", 7, 7, 512, 512, 3, 1, 3),
    ("s4_expand_1x1", 7, 7, 512, 2048, 1, 1, 3),
]


def calibrate_resnet50(batch=32, fmts=("NCHW", "NHWC"), shapes=None):
    """Conv roofline for the ResNet50 north-star config: measured TF/s for
    the distinct conv shapes (fwd and fwd+bwd), in both layouts, plus the
    count-weighted step-time lower bound per layout. Answers whether the
    b32/224^2 shapes underfill the MXU and whether the layout handed to
    XLA matters. ``shapes``: optional subset of _RESNET50_CONVS names —
    each (shape, layout, direction) costs two compiles over the remote
    compiler, so the full 13-shape sweep is ~10 minutes."""
    convs = [c for c in _RESNET50_CONVS
             if shapes is None or c[0] in shapes]
    out = {"device": str(jax.devices()[0].device_kind), "batch": batch,
           "method": "scan-slope (see module docstring)", "convs": {},
           "roofline": {}}
    for fmt in fmts:
        total = 0.0
        total_flops = 0.0
        unresolved = 0
        for name, h, w, cin, cout, k, s, cnt in convs:
            tf_f, dt_f = measure_conv(batch, h, w, cin, cout, k, k, s, fmt)
            tf_b, dt_b = measure_conv(batch, h, w, cin, cout, k, k, s, fmt,
                                      bwd=True)
            rec = out["convs"].setdefault(name, {
                "shape": [batch, h, w, cin, cout, k, s], "count": cnt})
            rec[fmt] = {
                "fwd_tflops": round(tf_f, 2) if tf_f else None,
                "bwd_tflops": round(tf_b, 2) if tf_b else None,
                "fwd_ms": round(dt_f * 1e3, 3) if dt_f else None,
                "bwd_ms": round(dt_b * 1e3, 3) if dt_b else None}
            _log(f"{fmt} {name}: fwd {tf_f and round(tf_f, 1)} / "
                 f"bwd {tf_b and round(tf_b, 1)} TF/s")
            if dt_f and dt_b:
                total += cnt * (dt_f + dt_b)
                total_flops += cnt * 3 * 2.0 * batch * (h // s) * (w // s) \
                    * cout * cin * k * k
            else:
                unresolved += 1
        out["roofline"][fmt] = {
            "conv_time_ms": round(total * 1e3, 2),
            "blended_conv_tflops": round(total_flops / total / 1e12, 2)
            if total else None,
            "unresolved_shapes": unresolved,
            "note": ("lower bound: conv kernel time only — BN/ReLU/pool/"
                     "optimizer ride free; real step time must exceed it; "
                     "shapes below timing resolution excluded"),
        }
    return out


def calibrate(batch=8, seq=1024, hidden=768, heads=12, layers=12,
              vocab=50304, ffn_mult=4):
    """Roofline for the bench GPT-124M config at (batch, seq)."""
    tokens = batch * seq
    head_dim = hidden // heads

    gemms = {
        # name: (m, k, n, count per step)
        "qkv": (tokens, hidden, 3 * hidden, layers),
        "attn_proj": (tokens, hidden, hidden, layers),
        "ffn_up": (tokens, hidden, ffn_mult * hidden, layers),
        "ffn_down": (tokens, ffn_mult * hidden, hidden, layers),
        "lm_head": (tokens, hidden, vocab, 1),
    }

    out = {"device": str(jax.devices()[0].device_kind),
           "batch": batch, "seq": seq,
           "method": "scan-slope (see module docstring)", "gemms": {}}

    for s in (8192,):
        tf, dt = measure_matmul(s, s, s)
        out["gemms"][f"square_{s}"] = {
            "shape": [s, s, s], "tflops": round(tf, 2),
            "ms": round(dt * 1e3, 3)}
        _log(f"square_{s}: {tf:.1f} TF/s ({dt*1e3:.3f} ms)")

    total_matmul_time = 0.0
    total_matmul_flops = 0.0
    for name, (m, k, n, cnt) in gemms.items():
        tf, dt = measure_matmul(m, k, n)
        tf_dx, dt_dx = measure_matmul(m, n, k)      # dX = dY @ W^T
        tf_dw, dt_dw = measure_matmul(k, m, n)      # dW = X^T @ dY
        out["gemms"][name] = {
            "shape": [m, k, n], "count": cnt,
            "fwd_tflops": round(tf, 2), "dx_tflops": round(tf_dx, 2),
            "dw_tflops": round(tf_dw, 2),
            "fwd_ms": round(dt * 1e3, 3)}
        _log(f"{name}: fwd {tf:.1f} / dx {tf_dx:.1f} / dw {tf_dw:.1f} TF/s")
        total_matmul_time += cnt * (dt + dt_dx + dt_dw)
        total_matmul_flops += cnt * 3 * (2.0 * m * k * n)

    att = measure_attention(batch, heads, seq, head_dim)
    out["attention"] = dict(att, shape=[batch, heads, seq, head_dim],
                            causal=True)
    _log(f"attention: fwd {att['fwd']['tflops']} TF/s "
         f"({att['fwd']['ms']} ms), bwd {att['bwd']['tflops']} TF/s "
         f"({att['bwd']['ms']} ms)")
    att_time = layers * (att["fwd"]["ms"] + att["bwd"]["ms"]) / 1e3

    # per-kernel fwd/bwd breakdown (ISSUE 11): the backward-ratio
    # regression is tracked in every calibration run
    out["kernels"] = kernel_breakdown(batch, seq, hidden, heads, layers,
                                      att=att)

    step_lb = total_matmul_time + att_time
    out["roofline"] = {
        "matmul_time_ms": round(total_matmul_time * 1e3, 2),
        "attention_time_ms": round(att_time * 1e3, 2),
        "step_time_lower_bound_ms": round(step_lb * 1e3, 2),
        "blended_matmul_tflops": round(
            total_matmul_flops / total_matmul_time / 1e12, 2),
        "note": ("lower bound: GEMM+attention kernel time only, zero "
                 "elementwise/softmax/optimizer/dispatch; real step time "
                 "must exceed this"),
    }
    return out


if __name__ == "__main__":
    res = calibrate()
    print(json.dumps(res, indent=2))
    if len(sys.argv) > 1:
        with open(sys.argv[1], "w") as f:
            json.dump(res, f, indent=2)
