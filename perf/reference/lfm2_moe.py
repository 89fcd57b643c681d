"""LFM2-MoE (HF ``lfm2_moe``: LiquidAI's LFM2-24B-A2B) as one chip of
an expert-parallel deployment computes it, written plainly.

``h = E[ids]``; each layer ``h += operator(RMSNorm(h)); h +=
feed_forward(RMSNorm(h))``; ``logits = RMSNorm(h) E^T`` (tied).

* operator ``conv``: ``B, C, X = split3(a W_in)``; ``u = B * X``;
  ``c_t = k_0 u_{t-2} + k_1 u_{t-1} + k_2 u_t`` per channel, zeros left
  of the sequence; ``(C * c) W_out``.
* operator ``full_attention``: q (32 heads), k, v (8 heads) of 64;
  RMSNorm over each q and k head; half-rotation RoPE; causal softmax
  attention, each key/value head serving ``heads / kv_heads`` query
  heads; an output projection.  Scores are made a block of queries at a
  time (32 x 8192 x 8192 float32 scores are 8.6 GB).
* feed-forward, dense: ``W_2(silu(W_1 f) * W_3 f)``.
* feed-forward, sparse: ``s = sigmoid(f W_r)`` over ALL the router's
  experts; ``sel = top_k(s + b)``; ``w = s[sel] / (sum s[sel] + 1e-6) *
  routed_scaling_factor``; the sum over the experts HELD here
  (``expert_offset`` on, ``num_experts`` of them) of ``w_e`` times the
  expert's SwiGLU.  What the absent experts would add is left out, and
  that partial sum goes on to the next layer.  Each held expert is
  applied to every token and multiplied by its weight, zero where it
  was not selected: no gather, no grouped product.

Leaves are per layer (``layers.<i>.*``), not stacked; matrices are
[in, out]; the held experts of a layer are one leaf [held, in, out].
Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C

QUERY_BLOCK = 512


def plan(cfg):
    """[(operator, feed-forward)] of the layers kept: the operator of
    each published layer in ``layers_kept``, a dense feed-forward in
    the first ``num_dense_layers`` of them and a sparse one after."""
    return [(cfg["layer_types"][i],
             "dense" if at < cfg["num_dense_layers"] else "sparse")
            for at, i in enumerate(cfg["layers_kept"])]


def expert_bias(cfg):
    """[sparse layers, router width] float32: the selection bias, a
    constant of the configuration (``expert_bias_seed``,
    ``expert_bias_std``), not of ``--seed``."""
    n = sum(ffn == "sparse" for _, ffn in plan(cfg))
    rng = np.random.default_rng(cfg["expert_bias_seed"])
    return (cfg["expert_bias_std"] * rng.standard_normal(
        (n, cfg["published"]["num_experts"]))).astype(np.float32)


def table(cfg):
    """name -> (shape, kind, std) of every leaf.  Every leaf is one the
    program holds in the compute type under AMP O2 (``decorate`` keeps
    only LayerNorm-family weights in float32, and RMSNorm is not of
    it)."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    layers = plan(cfg)
    out = 0.02 / math.sqrt(2 * len(layers))
    t = {"embed": ((v, h), "normal_low", 0.02),
         "final_norm": ((h,), "ones_low", 0.02)}
    for i, (op, ffn) in enumerate(layers):
        p = f"layers.{i}."
        t[p + "operator_norm"] = ((h,), "ones_low", 0.02)
        t[p + "ffn_norm"] = ((h,), "ones_low", 0.02)
        if op == "conv":
            taps = cfg["conv_L_cache"]
            t[p + "conv.in_proj"] = ((h, 3 * h), "normal_low", 0.02)
            t[p + "conv.taps"] = ((taps, h), "normal_low",
                                  1.0 / math.sqrt(taps))
            t[p + "conv.out_proj"] = ((h, h), "normal_low", out)
        else:
            t[p + "attn.q"] = ((h, heads * d), "normal_low", 0.02)
            t[p + "attn.k"] = ((h, kv * d), "normal_low", 0.02)
            t[p + "attn.v"] = ((h, kv * d), "normal_low", 0.02)
            t[p + "attn.o"] = ((heads * d, h), "normal_low", out)
            t[p + "attn.q_norm"] = ((d,), "ones_low", 0.02)
            t[p + "attn.k_norm"] = ((d,), "ones_low", 0.02)
        if ffn == "dense":
            f = cfg["intermediate_size"]
            t[p + "mlp.w1"] = ((h, f), "normal_low", 0.02)
            t[p + "mlp.w3"] = ((h, f), "normal_low", 0.02)
            t[p + "mlp.w2"] = ((f, h), "normal_low", out)
        else:
            f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
            t[p + "moe.router"] = ((h, cfg["published"]["num_experts"]),
                                   "normal_low", 0.02)
            t[p + "moe.w1"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w3"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w2"] = ((held, f, h), "normal_low", out)
    return t


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def short_conv(a, w, mm):
    """The gated short-convolution operator on [B, S, H]."""
    b, c, x = jnp.split(mm.act(mm.dot(a, w["conv.in_proj"])), 3, axis=-1)
    u, taps = b * x, w["conv.taps"]
    k, s = taps.shape[0], a.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[:, j:j + s] for j in range(k))
    return mm.dot(mm.act(c * conv), w["conv.out_proj"])


def rope(x, theta):
    """Half-rotation RoPE on [B, S, H, D]; the angles in float64."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d // 2) * 2.0 / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v, mm, block=QUERY_BLOCK):
    """Softmax attention over [B, S, H, D] heads (k and v already
    repeated to q's heads), the scores of ``block`` queries at a time;
    each block is recomputed in the backward, so one block's scores
    are all that is ever held."""
    b, s, h, d = q.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} positions in blocks of {block}")
    keys = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        qb, first = args
        sc = mm.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        keep = keys[None, :] <= first + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return mm.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def attention(a, w, cfg, mm):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    b, s, _ = a.shape
    q = mm.act(mm.dot(a, w["attn.q"])).reshape(b, s, heads, -1)
    k = mm.act(mm.dot(a, w["attn.k"])).reshape(b, s, kv, -1)
    v = mm.act(mm.dot(a, w["attn.v"])).reshape(b, s, kv, -1)
    q = mm.act(rope(rms_norm(q, w["attn.q_norm"], eps), theta))
    k = mm.act(rope(rms_norm(k, w["attn.k_norm"], eps), theta))
    # query head j reads key/value head j // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    o = causal_attention(q, k, v, mm).reshape(b, s, -1)
    return mm.dot(mm.act(o), w["attn.o"])


def swiglu(f, w1, w3, w2, mm):
    return mm.dot(mm.act(jax.nn.silu(mm.dot(f, w1)) * mm.dot(f, w3)), w2)


def route(f, router, bias, top_k, scaling, mm):
    """[.., E] combine weights over all the router's experts: the
    normalised scores of the ``top_k`` selected, zero elsewhere."""
    s = jax.nn.sigmoid(mm.dot(f, router).astype(jnp.float32))
    _, sel = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scaling
    return jnp.sum(jax.nn.one_hot(sel, s.shape[-1], dtype=w.dtype)
                   * w[..., None], axis=-2)


def sparse_ffn(f, w, bias, top_k, scaling, offset, mm):
    """The part of the sparse layer's result that the experts
    ``offset .. offset + held`` give (``w['moe.w1']`` is [held, ..])."""
    weights = route(f, w["moe.router"], bias, top_k, scaling, mm)
    out = jnp.zeros_like(f)
    for e in range(w["moe.w1"].shape[0]):
        y = swiglu(f, w["moe.w1"][e], w["moe.w3"][e], w["moe.w2"][e], mm)
        out = out + weights[..., offset + e, None] * y
    return out


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final RMSNorm."""
    eps, biases, at = cfg["norm_eps"], expert_bias(cfg), 0
    x = mm.act(params["embed"][ids])
    for i, (op, ffn) in enumerate(plan(cfg)):
        w = {k[len(f"layers.{i}."):]: p for k, p in params.items()
             if k.startswith(f"layers.{i}.")}
        bias = None
        if ffn == "sparse":
            bias, at = jnp.asarray(biases[at]), at + 1

        @jax.checkpoint
        def layer(x, w, op=op, ffn=ffn, bias=bias):
            a = mm.act(rms_norm(x, w["operator_norm"], eps))
            x = mm.act(x + (short_conv(a, w, mm) if op == "conv"
                            else attention(a, w, cfg, mm)))
            f = mm.act(rms_norm(x, w["ffn_norm"], eps))
            if ffn == "dense":
                y = swiglu(f, w["mlp.w1"], w["mlp.w3"], w["mlp.w2"], mm)
            else:
                y = sparse_ffn(f, w, bias, cfg["num_experts_per_tok"],
                               cfg["routed_scaling_factor"],
                               cfg["expert_offset"], mm)
            return mm.act(x + y)

        x = layer(x, w)
    return mm.act(rms_norm(x, params["final_norm"], eps))


def logits(params, cfg, ids, mm=None):
    mm = mm or C.Matmul()
    return mm.dot(hidden(params, cfg, ids, mm), params["embed"].T)


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
