"""Mellum (HF ``mellum``: JetBrains' Mellum2-12B-A2.5B) as one chip of
an expert-parallel deployment computes it, written plainly.

``h = E[ids]``; each layer ``h += attention(RMSNorm(h)); h +=
routed(RMSNorm(h))`` (eps ``rms_norm_eps``); ``logits = RMSNorm(h)
W_head`` (untied).

* attention: ``q = a W_q`` -> [S, 32, 128], ``k = a W_k``, ``v = a W_v``
  -> [S, 4, 128], no bias; half-rotation RoPE on q and k from the
  layer type's table; each key/value head serves ``heads / kv_heads``
  query heads; ``o = softmax(q k^T / sqrt(128) + mask) v``; ``o W_o``.
  The mask is built from positions: key ``j`` is seen by query ``i``
  where ``j <= i`` and, on a ``sliding_attention`` layer, ``i - j <
  sliding_window`` (the window's ``sliding_window`` keys, ``i`` itself
  among them).  Scores are made a block of 128 queries at a time.
* rotary tables (``rope_parameters``, one group per layer type), pair
  ``i`` of 64 at ``position * inv_freq_i``:
  ``sliding_attention``, ``rope_type`` default: ``inv_freq_i = theta **
  (-2i / 128)``;
  ``full_attention``, ``rope_type`` yarn (HF
  ``_compute_yarn_parameters``): ``ext_i = theta ** (-2i / 128)``,
  ``int_i = ext_i / factor``, ``c(r) = 128 ln(original_max / (2 pi r)) /
  (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), 127)``, ``ramp_i = clip((i - low) / (high -
  low), 0, 1)``, ``inv_freq_i = int_i ramp_i + ext_i (1 - ramp_i)``, and
  cos and sin both times ``attention_factor``.
* experts: ``p = softmax(f W_r)`` in float32 over ALL the router's
  experts; ``sel = top_k(p)``; ``w = p[sel] / sum p[sel]``; the sum
  over the experts HELD here (``expert_offset`` on, ``num_experts`` of
  them) of ``w_e`` times the expert's SwiGLU ``W_2(silu(W_1 f) * W_3
  f)``.  What the absent experts would add is left out, and that
  partial sum goes on to the next layer.  Each held expert is applied
  to every token and multiplied by its weight, zero where it was not
  selected: no gather, no grouped product.  Where the configuration's
  ``train_router`` is false the weights ``w`` are constants of the
  backward: a lone share reads the absent experts' ``dL/dw_e`` as zero,
  and the router's gradient it could form from the held ones alone
  moves its own routed share (grows it sixfold in 130 steps where a
  token's own part leads the stream, empties it where it does not:
  PERF.md section 6, PR 42) as no deployment's does, whose balancing
  rule a share has not.

Departures from the published description: no per-head q / k norm (the
config has no key for one); the MTP head the model card mentions is not
among the config's keys and is left out; ``intermediate_size`` is unused
(every ``mlp_layer_types`` entry is ``sparse``); no auxiliary loss (the
published file has no weight for one); the experts absent from this
chip add nothing; the embedding is drawn N(0, ``EMBED_STD``) and not at
the other leaves' 0.02 (below).

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

# queries whose scores are held at a time: 32 heads x 128 x 8,192 float32
# scores are 0.13 GB, and a block's backward holds four such arrays; at
# the other references' 512 the gradient program asked for 60 MB more
# than the chip has beside this configuration's 9.99 GB of float32 state
QUERY_BLOCK = 128
LOSS_BLOCK = 1024       # positions whose logits are held at a time
# the embedding's standard deviation: a unit residual stream at the
# start, as a trained model's and as the families that multiply the
# lookup by sqrt(hidden) start with.  Tokens are drawn at random, so a
# layer's attention adds to every position much the same average; at
# 0.02 that common part outweighs a token's own from the second layer
# on, every token of a row then picks the same eight experts and a
# layer's slots routed here are 0 or a multiple of the row (at the
# published widths, initial values, 2,048 positions, 2,048 slots at an
# even spread: 2,062 / 1,309 / 3,225 / 7 / 2,636 / 3,440 / 5,842 /
# 2,302 by layer at 0.02, 1,955 to 2,158 in all eight at 1)
EMBED_STD = 1.0


def plan(cfg):
    """The attention type of each layer kept: the published layers
    ``layers_kept`` of ``layer_types``; every one of them is sparse."""
    kept = cfg["layers_kept"]
    if {cfg["mlp_layer_types"][i] for i in kept} != {"sparse"}:
        raise ValueError("every layer of this family is sparse")
    return [cfg["layer_types"][i] for i in kept]


def table(cfg):
    """name -> (shape, kind, std) of every leaf.  Every leaf is one the
    program holds in the compute type under AMP O2."""
    h, vocab, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    layers = plan(cfg)
    out = 0.02 / math.sqrt(2 * len(layers))
    t = {"embed": ((vocab, h), "normal_low", EMBED_STD),
         "head": ((h, vocab), "normal_low", 0.02),
         "final_norm": ((h,), "ones_low", 0.02)}
    for i in range(len(layers)):
        p = f"layers.{i}."
        t[p + "input_norm"] = ((h,), "ones_low", 0.02)
        t[p + "ffn_norm"] = ((h,), "ones_low", 0.02)
        t[p + "attn.q"] = ((h, heads * d), "normal_low", 0.02)
        t[p + "attn.k"] = ((h, kv * d), "normal_low", 0.02)
        t[p + "attn.v"] = ((h, kv * d), "normal_low", 0.02)
        t[p + "attn.o"] = ((heads * d, h), "normal_low", out)
        t[p + "moe.router"] = ((h, cfg["published"]["num_experts"]),
                               "normal_low", 0.02)
        t[p + "moe.w1"] = ((held, h, f), "normal_low", 0.02)
        t[p + "moe.w3"] = ((held, h, f), "normal_low", 0.02)
        t[p + "moe.w2"] = ((held, f, h), "normal_low", out)
    return t


def parameters(cfg):
    """How many numbers ``table`` holds."""
    return sum(math.prod(shape) for shape, _, _ in table(cfg).values())


def inv_freq(d, p):
    """float64 [d // 2]: the frequencies of one ``rope_parameters``
    group, and the factor its cos and sin are multiplied by."""
    theta = p["rope_theta"]
    ext = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if p["rope_type"] == "default":
        return ext, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")

    def c(r):
        return d * math.log(p["original_max_position_embeddings"]
                            / (2 * math.pi * r)) / (2 * math.log(theta))

    low = max(math.floor(c(p["beta_fast"])), 0)
    high = min(math.ceil(c(p["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ext / p["factor"] * ramp + ext * (1.0 - ramp), \
        p["attention_factor"]


def rope(x, p):
    """Half-rotation RoPE on [B, S, H, D] by one ``rope_parameters``
    group; the angles in float64."""
    s, d = x.shape[1], x.shape[-1]
    inv, factor = inv_freq(d, p)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def masked_attention(q, k, v, window, mm, block=QUERY_BLOCK):
    """Softmax attention over [B, S, H, D] heads (k and v already
    repeated to q's heads): query ``i`` sees key ``j`` where ``j <= i``
    and, with a ``window``, ``i - j < window``.  The scores of ``block``
    queries at a time; each block is recomputed in the backward, so one
    block's scores are all that is ever held."""
    b, s, h, d = q.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} positions in blocks of {block}")
    keys = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(args):
        qb, first = args
        sc = mm.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        at = first + jnp.arange(block)[:, None]
        keep = keys <= at
        if window is not None:
            keep = keep & (at - keys < window)
        p = jax.nn.softmax(jnp.where(keep, sc, -jnp.inf), axis=-1)
        return mm.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = q.reshape(b, s // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(one, (blocks, jnp.arange(0, s, block)))
    return out.swapaxes(0, 1).reshape(b, s, h, d)


def attention(a, w, kind, cfg, mm):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    turn = cfg["rope_parameters"][kind]
    b, s, _ = a.shape
    q = mm.act(mm.dot(a, w["attn.q"])).reshape(b, s, heads, -1)
    k = mm.act(mm.dot(a, w["attn.k"])).reshape(b, s, kv, -1)
    v = mm.act(mm.dot(a, w["attn.v"])).reshape(b, s, kv, -1)
    q, k = mm.act(rope(q, turn)), mm.act(rope(k, turn))
    # query head j reads key/value head j // (heads / kv)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = masked_attention(q, k, v, window, mm).reshape(b, s, -1)
    return mm.dot(mm.act(o), w["attn.o"])


def route(f, router, top_k, mm):
    """[.., E] combine weights over all the router's experts: the
    ``top_k`` largest of a float32 softmax over them all, divided by
    their sum; zero elsewhere."""
    p = jax.nn.softmax(mm.dot(f, router).astype(jnp.float32), axis=-1)
    picked, sel = jax.lax.top_k(p, top_k)
    w = picked / picked.sum(-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(sel, p.shape[-1], dtype=w.dtype)
                   * w[..., None], axis=-2)


def routed_ffn(f, w, cfg, mm, offset=None):
    """The part of the routed experts' result that the experts
    ``offset .. offset + held`` give (``w['moe.w1']`` is [held, ..]),
    an expert at a time; ``offset`` is the configuration's
    ``expert_offset`` unless given."""
    offset = cfg["expert_offset"] if offset is None else offset
    weights = route(f, w["moe.router"], cfg["num_experts_per_tok"], mm)
    if not cfg["train_router"]:
        weights = jax.lax.stop_gradient(weights)

    # recomputed in the backward, so that no expert's result is held
    # for its weight's gradient: one expert's rows at a time
    @jax.checkpoint
    def weighted(f, weight, w1, w3, w2):
        return weight[..., None] * swiglu(f, w1, w3, w2, mm)

    out = jnp.zeros_like(f)
    for e in range(w["moe.w1"].shape[0]):
        out = out + weighted(f, weights[..., offset + e], w["moe.w1"][e],
                             w["moe.w3"][e], w["moe.w2"][e])
    return out


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final RMSNorm."""
    eps = cfg["rms_norm_eps"]
    x = mm.act(params["embed"][ids])
    for i, kind in enumerate(plan(cfg)):
        w = {k[len(f"layers.{i}."):]: p for k, p in params.items()
             if k.startswith(f"layers.{i}.")}

        @jax.checkpoint
        def layer(x, w, kind=kind):
            a = mm.act(rms_norm(x, w["input_norm"], eps))
            x = mm.act(x + attention(a, w, kind, cfg, mm))
            f = mm.act(rms_norm(x, w["ffn_norm"], eps))
            return mm.act(x + routed_ffn(f, w, cfg, mm))

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
        x = hidden(params, cfg, ids, mm)
        block = min(LOSS_BLOCK, x.shape[1])
        if x.shape[1] % block:
            raise ValueError(f"{x.shape[1]} positions in blocks of {block}")

        # the head and the loss a block of positions at a time, each
        # recomputed in the backward: the float32 logits of 8,192
        # positions, their log-softmax and its gradient are 0.4 GB each
        @jax.checkpoint
        def one(args):
            xb, lb = args
            lg = mm.dot(xb, params["head"]).astype(jnp.float32)
            logp = jax.nn.log_softmax(lg, axis=-1)
            return jnp.sum(jnp.take_along_axis(logp, lb[..., None], axis=-1))

        def blocks(a):
            return a.reshape(a.shape[0], -1, block, *a.shape[2:]).swapaxes(
                0, 1)

        total = -jnp.sum(jax.lax.map(one, (blocks(x), blocks(labels)))) \
            / n_tokens
        return total, [total]
    return fn
