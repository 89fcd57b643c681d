"""Kimi-Linear decoder (HF ``kimi_linear``: Moonshot's
Kimi-Linear-48B-A3B) as one chip of an expert-parallel deployment
computes it, written plainly.

``h = E[ids]``; each layer ``h += operator(RMSNorm(h)); h +=
feed_forward(RMSNorm(h))`` (eps ``rms_norm_eps``); ``logits =
RMSNorm(h) W_head`` (untied).

* operator ``kda`` (Kimi Delta Attention), ``H`` heads of ``d``:
  ``q, k, v = silu(conv4(a W_q)), silu(conv4(a W_k)), silu(conv4(a
  W_v))`` (``conv4``: a depthwise causal convolution, ``out[t] = sum_j
  taps[j] x[t - 3 + j]``), each [S, H, d]; ``q`` and ``k`` divided by
  their L2 norm per head (``x rsqrt(sum x^2 + 1e-6)``), ``q`` times
  ``1 / sqrt(d)``; ``g = -exp(A_log) softplus(a W_fa W_fb + dt_bias)``
  [S, H, d], the decay's logarithm per channel (``A_log`` per head);
  ``beta = sigmoid(a W_b)`` [S, H].  Then TOKEN BY TOKEN, per head, with
  a state ``S`` [d, d] that starts at zero::

      S <- diag(exp(g_t)) S
      S <- S + beta_t k_t (v_t - S^T k_t)^T
      o_t = S^T q_t

  nothing chunked and no WY form: a ``lax.scan`` over the tokens of a
  block nested in a scan over blocks of 64, with ``jax.checkpoint`` at
  the block edges, so the gradient through a row's 8,192 steps holds
  the block-edge states and one block's steps.  The state's products
  are sums of elementwise float32 products (no matmul unit).  Then
  ``o = RMSNorm_d(o) * sigmoid(a W_ga W_gb)`` per head and ``o W_o``.
  Eight heads at a time (their columns of the projections, their rows
  of ``W_o``), each group recomputed in the backward.
* operator ``mla`` (latent attention WITHOUT rotation,
  ``mla_use_nope``): as ``reference/deepseek_v3.py`` with the
  ``qk_rope_head_dim`` dimensions of q and of the one shared key head
  used as they are; scores eight heads and a block of 512 queries at
  a time.
* feed-forward, dense (layers below ``first_k_dense_replace``) and
  sparse (router over ALL ``published.num_experts``, sigmoid scores,
  top-``num_experts_per_token`` of scores + bias, renormalised, times
  ``routed_scaling_factor``; the ``num_experts`` experts held here one
  at a time, PLUS the shared expert once): ``reference/deepseek_v3.py``'s
  ``routed_ffn``.

Departures from the published model are the configuration's
``assumed``.  Leaves are per layer (``layers.<i>.*``); matrices are
[in, out].  Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
from . import deepseek_v3 as D
from .deepseek_v3 import ROUTER_NORM_EPS, causal_attention  # noqa: F401
from .lfm2_moe import rms_norm, swiglu

TOKEN_BLOCK = 64
HEADS_AT_A_TIME = 8       # of an operator's 32: memory, not arithmetic
L2_EPS = 1e-6


def plan(cfg):
    """[(operator, feed-forward)] of the layers kept: ``layers_kept``
    are published layer numbers counted from 1, as
    ``linear_attn_config`` counts them."""
    lin = cfg["linear_attn_config"]
    out = []
    for n in cfg["layers_kept"]:
        if n in lin["kda_layers"]:
            op = "kda"
        elif n in lin["full_attn_layers"]:
            op = "mla"
        else:
            raise ValueError(f"layer {n} is in neither list of "
                             f"linear_attn_config")
        out.append((op, "dense" if n <= cfg["first_k_dense_replace"]
                    else "sparse"))
    return out


def expert_bias(cfg):
    """[sparse layers, router width] float32: the selection bias, a
    constant of the configuration, not of ``--seed``."""
    n = sum(ffn == "sparse" for _, ffn in plan(cfg))
    rng = np.random.default_rng(cfg["expert_bias_seed"])
    return (cfg["expert_bias_std"] * rng.standard_normal(
        (n, cfg["published"]["num_experts"]))).astype(np.float32)


def table(cfg):
    """name -> (shape, kind, std) of every leaf; every leaf is one the
    program holds in the compute type under AMP O2."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    kh, kd = lin["num_heads"], lin["head_dim"]
    taps = lin["short_conv_kernel_size"]
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    layers = plan(cfg)
    out = 0.02 / math.sqrt(2 * len(layers))
    t = {"embed": ((vocab, h), "normal_low", 0.02),
         "head": ((h, vocab), "normal_low", 0.02),
         "final_norm": ((h,), "ones_low", 0.02)}

    def mlp(prefix, width):
        t[prefix + ".w1"] = ((h, width), "normal_low", 0.02)
        t[prefix + ".w3"] = ((h, width), "normal_low", 0.02)
        t[prefix + ".w2"] = ((width, h), "normal_low", out)

    for i, (op, ffn) in enumerate(layers):
        p = f"layers.{i}."
        t[p + "input_norm"] = ((h,), "ones_low", 0.02)
        t[p + "ffn_norm"] = ((h,), "ones_low", 0.02)
        if op == "kda":
            for x in "qkv":
                t[p + f"kda.{x}"] = ((h, kh * kd), "normal_low", 0.02)
                t[p + f"kda.{x}_conv"] = ((taps, kh * kd), "normal_low",
                                          taps ** -0.5)
            t[p + "kda.f_a"] = ((h, kd), "normal_low", 0.02)
            t[p + "kda.f_b"] = ((kd, kh * kd), "normal_low", 0.02)
            t[p + "kda.A_log"] = ((kh,), "normal_low", cfg["A_log_std"])
            t[p + "kda.dt_bias"] = ((kh * kd,), "normal_low",
                                    cfg["dt_bias_std"])
            t[p + "kda.b"] = ((h, kh), "normal_low", 0.02)
            t[p + "kda.g_a"] = ((h, kd), "normal_low", 0.02)
            t[p + "kda.g_b"] = ((kd, kh * kd), "normal_low", 0.02)
            t[p + "kda.o_norm"] = ((kd,), "ones_low", 0.02)
            t[p + "kda.o"] = ((kh * kd, h), "normal_low", out)
        else:
            t[p + "attn.q"] = ((h, heads * (nope + rope)), "normal_low", 0.02)
            t[p + "attn.kv_down"] = ((h, rank + rope), "normal_low", 0.02)
            t[p + "attn.kv_norm"] = ((rank,), "ones_low", 0.02)
            t[p + "attn.kv_up"] = (
                (rank, heads * (nope + cfg["v_head_dim"])), "normal_low", 0.02)
            t[p + "attn.o"] = ((heads * cfg["v_head_dim"], h), "normal_low",
                               out)
        if ffn == "dense":
            mlp(p + "mlp", cfg["intermediate_size"])
        else:
            f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
            t[p + "moe.router"] = (
                (h, cfg["published"]["num_experts"]), "normal_low", 0.02)
            t[p + "moe.w1"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w3"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w2"] = ((held, f, h), "normal_low", out)
            mlp(p + "shared", cfg["num_shared_experts"] * f)
    return t


def causal_conv(x, taps):
    """``out[t] = sum_j taps[j] x[t - (K - 1) + j]`` on [B, S, C]."""
    k, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(k))


def delta_rule(q, k, v, g, beta, block=TOKEN_BLOCK):
    """The gated delta rule token by token on [B, S, H, d] (``g`` per
    channel of the keys, ``beta`` [B, S, H]) -> o [B, S, H, dv]."""
    b, s, h, dk = q.shape
    block = max(n for n in range(1, min(block, s) + 1) if s % n == 0)

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)

    q, k = l2(q) / math.sqrt(dk), l2(k)

    def token(state, xs):
        qt, kt, vt, gt, bt = xs                 # [B, H, d], bt [B, H]
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.sum(state * kt[..., None], axis=-2)          # S^T k
        state = state + kt[..., None] * (
            bt[..., None] * (vt - seen))[..., None, :]
        return state, jnp.sum(state * qt[..., None], axis=-2)   # S^T q

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(x):      # [B, S, ..] -> [S / block, block, B, ..]
        x = jnp.moveaxis(x, 1, 0)
        return x.reshape(s // block, block, *x.shape[1:])

    _, o = jax.lax.scan(
        tokens, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(blocks(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 1)


def kda_heads(a, w, d, eps, mm):
    """Kimi Delta Attention's part of the result that the heads whose
    columns (rows of ``kda.o``) ``w`` holds give."""
    b, s, _ = a.shape
    heads = w["kda.A_log"].shape[0]

    def branch(x):
        p = mm.act(mm.dot(a, w[f"kda.{x}"]))
        return mm.act(jax.nn.silu(causal_conv(p, w[f"kda.{x}_conv"]))
                      ).reshape(b, s, heads, d)

    f = mm.dot(mm.act(mm.dot(a, w["kda.f_a"])), w["kda.f_b"])
    g = -jnp.exp(w["kda.A_log"])[:, None] * jax.nn.softplus(
        (f + w["kda.dt_bias"]).reshape(b, s, heads, d))
    beta = jax.nn.sigmoid(mm.dot(a, w["kda.b"]))
    o = mm.act(delta_rule(branch("q"), branch("k"), branch("v"), g, beta))
    gate = mm.dot(mm.act(mm.dot(a, w["kda.g_a"])), w["kda.g_b"])
    o = rms_norm(o, w["kda.o_norm"], eps) \
        * jax.nn.sigmoid(gate.reshape(b, s, heads, d))
    return mm.dot(mm.act(o.reshape(b, s, -1)), w["kda.o"])


# leaves cut by head: along their columns, their rows, or per head
_BY_COLUMN = ("kda.q", "kda.k", "kda.v", "kda.q_conv", "kda.k_conv",
              "kda.v_conv", "kda.f_b", "kda.dt_bias", "kda.g_b")


def kda(a, w, cfg, mm):
    """The operator ``HEADS_AT_A_TIME`` heads at a time (every head's
    projections, gate, scan and rows of the output projection are its
    own; the two necks and the head norm's weight serve all), each
    group recomputed in the backward: one group's activations are all
    that is ever held.  The groups' parts add up to the layer's."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    step = min(HEADS_AT_A_TIME, heads)
    out = 0.0
    for lo in range(0, heads, step):
        hs, cs = slice(lo, lo + step), slice(lo * d, (lo + step) * d)
        part = {k: v for k, v in w.items() if k.startswith("kda.")}
        part.update({k: w[k][..., cs] for k in _BY_COLUMN})
        part.update({"kda.A_log": w["kda.A_log"][hs],
                     "kda.b": w["kda.b"][:, hs], "kda.o": w["kda.o"][cs]})
        out = out + jax.checkpoint(
            lambda a, part: kda_heads(a, part, d, cfg["rms_norm_eps"], mm))(
                a, part)
    return out


def latent_attention(a, w, cfg, mm):
    """MLA with the "rope" dimensions un-rotated; the scores
    ``HEADS_AT_A_TIME`` heads and 512 queries at a time."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    b, s, _ = a.shape
    q = mm.act(mm.dot(a, w["attn.q"])).reshape(b, s, heads, -1)
    down = mm.act(mm.dot(a, w["attn.kv_down"]))
    latent = mm.act(rms_norm(down[..., :rank], w["attn.kv_norm"],
                             cfg["rms_norm_eps"]))
    kv = mm.act(mm.dot(latent, w["attn.kv_up"])).reshape(b, s, heads, -1)
    k_pe = down[..., None, rank:]               # the one shared head

    @jax.checkpoint
    def group(q, kv, k_pe):
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:-1],
                                             k_pe.shape[-1]))], axis=-1)
        return causal_attention(q, k, v, mm)

    step = min(HEADS_AT_A_TIME, heads)
    o = jnp.concatenate([group(q[:, :, lo:lo + step], kv[:, :, lo:lo + step],
                               k_pe) for lo in range(0, heads, step)], axis=2)
    return mm.dot(mm.act(o.reshape(b, s, -1)), w["attn.o"])


def routed_ffn(f, w, bias, cfg, mm):
    """``reference/deepseek_v3.routed_ffn`` under this family's key for
    the experts a token selects."""
    return D.routed_ffn(f, w, bias, {
        **cfg, "num_experts_per_tok": cfg["num_experts_per_token"]}, mm)


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final RMSNorm."""
    eps, biases, at = cfg["rms_norm_eps"], expert_bias(cfg), 0
    x = mm.act(params["embed"][ids])
    for i, (op, ffn) in enumerate(plan(cfg)):
        w = {k[len(f"layers.{i}."):]: p for k, p in params.items()
             if k.startswith(f"layers.{i}.")}
        bias = None
        if ffn == "sparse":
            bias, at = jnp.asarray(biases[at]), at + 1

        @jax.checkpoint
        def layer(x, w, op=op, ffn=ffn, bias=bias):
            a = mm.act(rms_norm(x, w["input_norm"], eps))
            operator = kda if op == "kda" else latent_attention
            x = mm.act(x + operator(a, w, cfg, mm))
            f = mm.act(rms_norm(x, w["ffn_norm"], eps))
            if ffn == "dense":
                y = swiglu(f, w["mlp.w1"], w["mlp.w3"], w["mlp.w2"], mm)
            else:
                y = routed_ffn(f, w, bias, cfg, mm) + swiglu(
                    f, w["shared.w1"], w["shared.w3"], w["shared.w2"], mm)
            return mm.act(x + y)

        x = layer(x, w)
    return mm.act(rms_norm(x, params["final_norm"], eps))


def logits(params, cfg, ids, mm=None):
    mm = mm or C.Matmul()
    return mm.dot(hidden(params, cfg, ids, mm), params["head"])


def train_loss_rows(cfg, batch, mm=None):
    """The causal-LM loss of a block of rows, divided by the batch's
    token count (``labels`` are the next tokens)."""
    mm = mm or C.Matmul()
    n_tokens = batch["rows"] * batch["seq_len"]

    def fn(params, ids, labels):
        lg = logits(params, cfg, ids, mm).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        total = -jnp.sum(ll) / n_tokens
        return total, [total]
    return fn
