"""DeepSeek-V3-style decoder (HF ``deepseek_v3``: Moonshot's
Moonlight-16B-A3B) as one chip of an expert-parallel deployment
computes it, written plainly.

``h = E[ids]``; each layer ``h += latent_attention(RMSNorm(h)); h +=
feed_forward(RMSNorm(h))`` (eps ``rms_norm_eps``); ``logits =
RMSNorm(h) W_head`` (untied).

* latent attention (MLA, no query compression), ``H`` heads, per head
  ``n`` un-rotated + ``r`` rotated query/key dimensions and ``dv`` value
  dimensions: ``q = a W_q`` -> [S, H, n + r], split ``q_nope``, ``q_pe``;
  ``c, k_pe = split(a W_kva)`` -> [S, kv_lora_rank], [S, r];
  ``c = RMSNorm(c)`` (eps ``kv_norm_eps``); ``k_nope, v = split(c
  W_kvb)`` -> [S, H, n], [S, H, dv]; ``q_pe, k_pe = RoPE(q_pe, k_pe)``;
  ``k = [k_nope, k_pe broadcast over the heads]``, ``q = [q_nope,
  q_pe]``; ``o = softmax(q k^T / sqrt(n + r), causal) v``; ``o W_o``.
  Scores are made a block of 512 queries at a time.
* feed-forward, dense (the leading ``first_k_dense_replace`` layers):
  ``W_2(silu(W_1 f) * W_3 f)``.
* feed-forward, sparse: ``s = sigmoid(f W_r)`` over ALL the router's
  experts; ``sel = top_k(s + b)``; ``w = s[sel] / (sum s[sel] + 1e-20)
  * routed_scaling_factor``; the sum over the experts HELD here
  (``expert_offset`` on, ``n_routed_experts`` of them) of ``w_e`` times
  the expert's SwiGLU, PLUS the shared expert (one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``) added once.  What the
  absent experts would add is left out, and that partial sum goes on to
  the next layer.  Each held expert is applied to every token and
  multiplied by its weight, zero where it was not selected: no gather,
  no grouped product.

Departures from HF ``deepseek_v3`` (``modeling_deepseek_v3.py``):
RoPE turns the interleaved pairs ``(x[2i], x[2i+1])`` where HF first
permutes each rotated slice to ``[x[0], x[2], .., x[1], x[3], ..]`` and
then turns halves: q and k are permuted alike, so every score is equal;
``n_group`` = ``topk_group`` = 1, so HF's group-limited selection is
the plain top-k written here; the selection bias
(``e_score_correction_bias``) is a constant of the configuration, not a
trained buffer; no auxiliary loss (``seq_aux``'s weight is not in the
published file); the experts absent from this chip add nothing.

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
from .lfm2_moe import rms_norm, swiglu

QUERY_BLOCK = 512
ROUTER_NORM_EPS = 1e-20     # HF: topk_weights / (sum + 1e-20)


def plan(cfg):
    """["dense" | "sparse"] of the layers kept: the published layers
    ``layers_kept``, dense below ``first_k_dense_replace``."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "sparse"
            for i in cfg["layers_kept"]]


def expert_bias(cfg):
    """[sparse layers, router width] float32: the selection bias, a
    constant of the configuration (``expert_bias_seed``,
    ``expert_bias_std``), not of ``--seed``."""
    n = plan(cfg).count("sparse")
    rng = np.random.default_rng(cfg["expert_bias_seed"])
    return (cfg["expert_bias_std"] * rng.standard_normal(
        (n, cfg["published"]["n_routed_experts"]))).astype(np.float32)


def table(cfg):
    """name -> (shape, kind, std) of every leaf.  Every leaf is one the
    program holds in the compute type under AMP O2."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
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

    for i, ffn in enumerate(layers):
        p = f"layers.{i}."
        t[p + "input_norm"] = ((h,), "ones_low", 0.02)
        t[p + "ffn_norm"] = ((h,), "ones_low", 0.02)
        t[p + "attn.q"] = ((h, heads * (nope + rope)), "normal_low", 0.02)
        t[p + "attn.kv_down"] = ((h, rank + rope), "normal_low", 0.02)
        t[p + "attn.kv_norm"] = ((rank,), "ones_low", 0.02)
        t[p + "attn.kv_up"] = ((rank, heads * (nope + cfg["v_head_dim"])),
                               "normal_low", 0.02)
        t[p + "attn.o"] = ((heads * cfg["v_head_dim"], h), "normal_low", out)
        if ffn == "dense":
            mlp(p + "mlp", cfg["intermediate_size"])
        else:
            f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
            t[p + "moe.router"] = (
                (h, cfg["published"]["n_routed_experts"]), "normal_low", 0.02)
            t[p + "moe.w1"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w3"] = ((held, h, f), "normal_low", 0.02)
            t[p + "moe.w2"] = ((held, f, h), "normal_low", out)
            mlp(p + "shared", cfg["n_shared_experts"] * f)
    return t


def rope(x, theta):
    """RoPE on [B, S, H, D] over the interleaved pairs (x[2i],
    x[2i+1]), pair i at ``position * theta ** (-2i / D)``; the angles
    in float64."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d // 2) * 2.0 / d)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def causal_attention(q, k, v, mm, block=QUERY_BLOCK):
    """Softmax attention over [B, S, H, D] queries and keys and
    [B, S, H, Dv] values, the scores of ``block`` queries at a time;
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
    return out.swapaxes(0, 1).reshape(b, s, h, v.shape[-1])


def latent_attention(a, w, cfg, mm):
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, theta = cfg["qk_nope_head_dim"], cfg["rope_theta"]
    b, s, _ = a.shape
    q = mm.act(mm.dot(a, w["attn.q"])).reshape(b, s, heads, -1)
    down = mm.act(mm.dot(a, w["attn.kv_down"]))
    latent = mm.act(rms_norm(down[..., :rank], w["attn.kv_norm"],
                             cfg["kv_norm_eps"]))
    kv = mm.act(mm.dot(latent, w["attn.kv_up"])).reshape(b, s, heads, -1)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = mm.act(rope(q[..., nope:], theta))
    k_pe = mm.act(rope(down[..., None, rank:], theta))  # the one shared head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:-1],
                                         k_pe.shape[-1]))], axis=-1)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    o = causal_attention(q, k, v, mm).reshape(b, s, -1)
    return mm.dot(mm.act(o), w["attn.o"])


def route(f, router, bias, top_k, scaling, mm):
    """[.., E] combine weights over all the router's experts: the
    normalised scores of the ``top_k`` selected, zero elsewhere."""
    s = jax.nn.sigmoid(mm.dot(f, router).astype(jnp.float32))
    _, sel = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + ROUTER_NORM_EPS) * scaling
    return jnp.sum(jax.nn.one_hot(sel, s.shape[-1], dtype=w.dtype)
                   * w[..., None], axis=-2)


def routed_ffn(f, w, bias, cfg, mm):
    """The part of the routed experts' result that the experts
    ``expert_offset .. expert_offset + held`` give (``w['moe.w1']`` is
    [held, ..]), an expert at a time."""
    weights = route(f, w["moe.router"], bias, cfg["num_experts_per_tok"],
                    cfg["routed_scaling_factor"], mm)
    out = jnp.zeros_like(f)
    for e in range(w["moe.w1"].shape[0]):
        y = jax.checkpoint(lambda f, a, b, c: swiglu(f, a, b, c, mm))(
            f, w["moe.w1"][e], w["moe.w3"][e], w["moe.w2"][e])
        out = out + weights[..., cfg["expert_offset"] + e, None] * y
    return out


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final RMSNorm."""
    eps, biases, at = cfg["rms_norm_eps"], expert_bias(cfg), 0
    x = mm.act(params["embed"][ids])
    for i, ffn in enumerate(plan(cfg)):
        w = {k[len(f"layers.{i}."):]: p for k, p in params.items()
             if k.startswith(f"layers.{i}.")}
        bias = None
        if ffn == "sparse":
            bias, at = jnp.asarray(biases[at]), at + 1

        @jax.checkpoint
        def layer(x, w, ffn=ffn, bias=bias):
            a = mm.act(rms_norm(x, w["input_norm"], eps))
            x = mm.act(x + latent_attention(a, w, cfg, mm))
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
