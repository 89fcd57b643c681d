"""Laguna (HF ``laguna``: poolside's Laguna-XS.2, 33.4B-A3B) as one chip
of an expert-parallel deployment computes it, written plainly.

``h = E[ids]``; each layer ``n = RMSNorm(h); h += attention(n); f =
RMSNorm(h); h += feed_forward(f)`` (eps ``rms_norm_eps``); ``logits =
RMSNorm(h) W_head`` (untied).

* attention of layer ``l``: ``H_l = num_attention_heads_per_layer[l]``
  query heads (48 on ``full_attention``, 64 on ``sliding_attention``),
  8 key/value heads, ``d`` 128, no bias: ``q = n W_q`` -> [S, H_l, 128],
  ``k = n W_k``, ``v = n W_v`` -> [S, 8, 128]; the first ``r = 128 x
  partial_rotary_factor`` dimensions of each q and k head are rotated
  by the halves convention WITHIN those ``r`` (``x[:r/2]`` pairs with
  ``x[r/2:r]``) and the other ``128 - r`` pass through (HF's ``q_rot,
  q_pass``); each key/value head serves ``H_l / 8`` query heads; ``A =
  softmax(q k^T / sqrt(128) + mask) v``.  The mask is built from
  positions: key ``j`` is seen by query ``i`` where ``j <= i`` and, on
  a ``sliding_attention`` layer, ``i - j < sliding_window``.  Then the
  gate: ``g = sigmoid(n W_g)`` [S, H_l] (no bias), and ``o =
  concat_h(g_h A_h) W_o``.  One key/value head's group of query heads
  and a block of queries at a time.
* rotary tables (``rope_parameters``, one group per layer type), pair
  ``i`` of ``r / 2`` at ``position * inv_freq_i``:
  ``sliding_attention``, ``rope_type`` default, ``r`` 128: ``inv_freq_i
  = theta ** (-2i / r)``;
  ``full_attention``, ``rope_type`` yarn, ``r`` 64 (HF
  ``_compute_yarn_parameters`` with ``dim = r``): ``ext_i = theta **
  (-2i / r)``, ``int_i = ext_i / factor``, ``c(t) = r ln(original_max /
  (2 pi t)) / (2 ln theta)``, ``low = max(floor(c(beta_fast)), 0)``,
  ``high = min(ceil(c(beta_slow)), r - 1)``, ``ramp_i = clip((i - low)
  / (high - low), 0, 1)``, ``inv_freq_i = int_i ramp_i + ext_i (1 -
  ramp_i)``, and cos and sin both times ``attention_factor``.
* feed-forward: layer 0 (``mlp_layer_types`` ``dense``) one SwiGLU of
  ``intermediate_size``, ``W_2(silu(W_1 f) * W_3 f)``.  A ``sparse``
  layer ``shared(f) + routed(f)``: the shared expert is one SwiGLU of
  ``shared_expert_intermediate_size`` that every token passes; routed:
  ``s = sigmoid(f W_r)`` in float32 over ALL the router's experts,
  ``sel = top_k(s)``, ``w = moe_routed_scaling_factor s[sel] / (sum
  s[sel] + 1e-20)``, applied to the experts' OUTPUT
  (``moe_apply_router_weight_on_input`` false); the sum over the
  experts HELD here (``expert_offset`` on, ``num_experts`` of them) of
  ``w_e`` times the expert's SwiGLU of ``moe_intermediate_size``.  What
  the absent experts would add is left out, and that partial sum goes
  on to the next layer.  Each held expert is applied to every token and
  multiplied by its weight, zero where it was not selected: no gather,
  no grouped product.  Where the configuration's ``train_router`` is
  false the weights ``w`` are constants of the backward
  (``reference/mellum.py`` says why a lone share needs that).

Departures from the published description (the configuration's
``assumed`` has the grounds): ``gating`` is read as the per-head
sigmoid gate above; the router's score function has no key and is read
as the sigmoid recipe above, with no selection bias; no per-head q / k
norm; no auxiliary loss; the experts absent from this chip add
nothing; the embedding is drawn N(0, ``EMBED_STD``) and not at the
other leaves' 0.02 (``reference/mellum.py`` ``EMBED_STD``).

Leaves are per layer (``layers.<i>.*``), not stacked; matrices are
[in, out]; the held experts of a layer are one leaf [held, in, out].
Nothing here imports the program: the frequencies' formula and the
masked softmax are ``reference/mellum.py``'s (given this family's
widths), the sigmoid router ``reference/deepseek_v3.py``'s (given no
bias); the partial rotation, the gate and the layer are written here.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import common as C
# sigmoid scores, the selected ones renormalised (their sum plus
# ROUTER_NORM_EPS), times the scaling factor; given no selection bias
from .deepseek_v3 import ROUTER_NORM_EPS, route  # noqa: F401
from .lfm2_moe import rms_norm, swiglu
# the frequencies of a ``rope_parameters`` group over the width it is
# given, and softmax attention under a mask built from positions
from .mellum import EMBED_STD, LOSS_BLOCK, inv_freq, masked_attention

# queries whose scores are held at a time, of one key/value head's
# group of query heads (8 of a window layer's 64): 8 x 512 x 8,192
# float32 scores are 0.13 GB, and a block's backward holds four such
# arrays beside this configuration's 11.07 GB of float32 state
QUERY_BLOCK = 128
MLP_BLOCK = 1024        # positions of the dense MLP held at a time


def plan(cfg):
    """[(attention type, feed-forward type, query heads)] of the layers
    kept: the published layers ``layers_kept`` of ``layer_types``,
    ``mlp_layer_types`` and ``num_attention_heads_per_layer``."""
    return [(cfg["layer_types"][i], cfg["mlp_layer_types"][i],
             cfg["num_attention_heads_per_layer"][i])
            for i in cfg["layers_kept"]]


def table(cfg):
    """name -> (shape, kind, std) of every leaf.  Every leaf is one the
    program holds in the compute type under AMP O2."""
    h, vocab, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv, held = cfg["num_key_value_heads"], cfg["num_experts"]
    layers = plan(cfg)
    out = 0.02 / math.sqrt(2 * len(layers))
    t = {"embed": ((vocab, h), "normal_low", EMBED_STD),
         "head": ((h, vocab), "normal_low", 0.02),
         "final_norm": ((h,), "ones_low", 0.02)}

    def mlp(prefix, width, lead=()):
        t[prefix + ".w1"] = ((*lead, h, width), "normal_low", 0.02)
        t[prefix + ".w3"] = ((*lead, h, width), "normal_low", 0.02)
        t[prefix + ".w2"] = ((*lead, width, h), "normal_low", out)

    for i, (_, ffn, heads) in enumerate(layers):
        p = f"layers.{i}."
        t[p + "input_norm"] = ((h,), "ones_low", 0.02)
        t[p + "ffn_norm"] = ((h,), "ones_low", 0.02)
        t[p + "attn.q"] = ((h, heads * d), "normal_low", 0.02)
        t[p + "attn.k"] = ((h, kv * d), "normal_low", 0.02)
        t[p + "attn.v"] = ((h, kv * d), "normal_low", 0.02)
        # a gate starts near a half
        t[p + "attn.g"] = ((h, heads), "normal_low", 0.02)
        t[p + "attn.o"] = ((heads * d, h), "normal_low", out)
        if ffn == "dense":
            mlp(p + "mlp", cfg["intermediate_size"])
        else:
            t[p + "moe.router"] = ((h, cfg["published"]["num_experts"]),
                                   "normal_low", 0.02)
            mlp(p + "moe", cfg["moe_intermediate_size"], (held,))
            mlp(p + "shared", cfg["shared_expert_intermediate_size"])
    return t


def parameters(cfg):
    """How many numbers ``table`` holds."""
    return sum(math.prod(shape) for shape, _, _ in table(cfg).values())


def rotary_dim(d, p):
    return int(d * p.get("partial_rotary_factor", 1))


def rope(x, p):
    """Half-rotation RoPE on the first ``r`` dimensions of [B, S, H, D]
    heads by one ``rope_parameters`` group, the rest passed through;
    the angles in float64."""
    s, r = x.shape[1], rotary_dim(x.shape[-1], p)
    inv, factor = inv_freq(r, p)
    ang = np.arange(s, dtype=np.float64)[:, None] * inv
    ang = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(ang) * factor, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * factor, jnp.float32)[None, :, None, :]
    turn, keep = x[..., :r], x[..., r:]
    x1, x2 = jnp.split(turn, 2, axis=-1)
    turned = turn * cos + jnp.concatenate([-x2, x1], axis=-1) * sin
    return jnp.concatenate([turned, keep], axis=-1)


def attention(n, w, kind, heads, cfg, mm):
    """One key/value head's group of ``heads / kv`` query heads at a
    time (query head j reads key/value head ``j // (heads / kv)``: the
    group's columns of ``W_q`` and ``W_g``, its one head of ``W_k`` and
    ``W_v``, its rows of ``W_o``), each group recomputed in the
    backward: memory, not arithmetic."""
    kv, turn = cfg["num_key_value_heads"], cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    b, s, h = n.shape
    rep = heads // kv

    @jax.checkpoint
    def group(n, wq, wk, wv, wg, wo):
        q = mm.act(mm.dot(n, wq)).reshape(b, s, rep, -1)
        k = mm.act(mm.dot(n, wk)).reshape(b, s, 1, -1)
        v = mm.act(mm.dot(n, wv)).reshape(b, s, 1, -1)
        q, k = mm.act(rope(q, turn)), mm.act(rope(k, turn))
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
        a = masked_attention(q, k, v, window, mm, QUERY_BLOCK)
        g = jax.nn.sigmoid(mm.act(mm.dot(n, wg)))           # [B, S, rep]
        return mm.dot(mm.act(a * g[..., None]).reshape(b, s, -1), wo)

    # [kv, hidden, the group's columns]; a scan runs the groups one
    # after another and is compiled once (as a Python loop the program
    # was 3.27 GB of code beside 3.44 GB of temporaries: PERF.md)
    wq, wk, wv, wg = (w[f"attn.{x}"].reshape(h, kv, -1).swapaxes(0, 1)
                      for x in "qkvg")
    wo = w["attn.o"].reshape(kv, -1, h)
    out, _ = jax.lax.scan(lambda out, ws: (out + group(n, *ws), None),
                          jnp.zeros_like(n), (wq, wk, wv, wg, wo))
    return out


def routed_ffn(f, w, cfg, mm, offset=None):
    """The part of the routed experts' result that the experts
    ``offset .. offset + held`` give (``w['moe.w1']`` is [held, ..]),
    an expert at a time; ``offset`` is the configuration's
    ``expert_offset`` unless given."""
    offset = cfg["expert_offset"] if offset is None else offset
    weights = route(f, w["moe.router"], 0.0, cfg["num_experts_per_tok"],
                    cfg["moe_routed_scaling_factor"], mm)
    if not cfg["train_router"]:
        weights = jax.lax.stop_gradient(weights)

    # recomputed in the backward, so that no expert's result is held
    # for its weight's gradient: one expert's rows at a time
    @jax.checkpoint
    def weighted(f, weight, w1, w3, w2):
        return weight[..., None] * swiglu(f, w1, w3, w2, mm)

    # one after another, compiled once: a scan over the held experts
    held = w["moe.w1"].shape[0]
    here = jnp.moveaxis(weights[..., offset:offset + held], -1, 0)
    out, _ = jax.lax.scan(
        lambda out, xs: (out + weighted(f, *xs), None), jnp.zeros_like(f),
        (here, w["moe.w1"], w["moe.w3"], w["moe.w2"]))
    return out


def dense_mlp(f, w1, w3, w2, mm):
    """The dense layer's SwiGLU, ``MLP_BLOCK`` positions at a time, each
    block recomputed in the backward: 8,192 positions' three float32
    arrays of ``intermediate_size`` are 0.27 GB each."""
    b, s, h = f.shape
    block = min(MLP_BLOCK, s)
    if s % block:
        raise ValueError(f"{s} positions in blocks of {block}")
    one = jax.checkpoint(lambda fb: swiglu(fb, w1, w3, w2, mm))
    out = jax.lax.map(one, f.reshape(b, s // block, block, h).swapaxes(0, 1))
    return out.swapaxes(0, 1).reshape(b, s, h)


def feed_forward(f, w, ffn, cfg, mm):
    if ffn == "dense":
        return dense_mlp(f, w["mlp.w1"], w["mlp.w3"], w["mlp.w2"], mm)
    return routed_ffn(f, w, cfg, mm) + swiglu(
        f, w["shared.w1"], w["shared.w3"], w["shared.w2"], mm)


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final RMSNorm."""
    eps = cfg["rms_norm_eps"]
    x = mm.act(params["embed"][ids])
    for i, (kind, ffn, heads) in enumerate(plan(cfg)):
        w = {k[len(f"layers.{i}."):]: p for k, p in params.items()
             if k.startswith(f"layers.{i}.")}

        @jax.checkpoint
        def layer(x, w, kind=kind, ffn=ffn, heads=heads):
            n = mm.act(rms_norm(x, w["input_norm"], eps))
            x = mm.act(x + attention(n, w, kind, heads, cfg, mm))
            f = mm.act(rms_norm(x, w["ffn_norm"], eps))
            return mm.act(x + feed_forward(f, w, ffn, cfg, mm))

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
        # recomputed in the backward
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
