"""BERT as published (Devlin et al. 2018; the released ``modeling.py``):
word + position + segment embeddings under one LayerNorm, post-LayerNorm
encoder blocks, a tanh pooler over [CLS], and the two pre-training
heads: masked-LM (dense + GELU + LayerNorm, decoder tied to the word
embedding) and next-sentence.

Departures, both following ``paddle_tpu/models/bert.py`` so that the
two sides hold the same leaves: q, k and v are one fused [H, 3H]
projection (the release has three [H, H]; the same arithmetic), and
the masked-LM decoder has no output bias (the release adds one per
vocabulary row).  GELU is the release's exact erf form.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C


def table(cfg):
    h, n, v = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    f, p = cfg["intermediate_size"], cfg["max_position_embeddings"]
    out = 0.02 / math.sqrt(2 * n)
    return {
        "word": ((v, h), "normal_low", 0.02),
        "position": ((p, h), "normal_low", 0.02),
        "token_type": ((cfg["type_vocab_size"], h), "normal_low", 0.02),
        "emb_ln.weight": ((h,), "ones", 0.02),
        "emb_ln.bias": ((h,), "normal", 0.02),
        "blocks.attn.qkv.weight": ((n, h, 3 * h), "normal_low", 0.02),
        "blocks.attn.qkv.bias": ((n, 3 * h), "normal_low", 0.02),
        "blocks.attn.proj.weight": ((n, h, h), "normal_low", out),
        "blocks.attn.proj.bias": ((n, h), "normal_low", 0.02),
        "blocks.ln1.weight": ((n, h), "ones", 0.02),
        "blocks.ln1.bias": ((n, h), "normal", 0.02),
        "blocks.fc1.weight": ((n, h, f), "normal_low", 0.02),
        "blocks.fc1.bias": ((n, f), "normal_low", 0.02),
        "blocks.fc2.weight": ((n, f, h), "normal_low", out),
        "blocks.fc2.bias": ((n, h), "normal_low", 0.02),
        "blocks.ln2.weight": ((n, h), "ones", 0.02),
        "blocks.ln2.bias": ((n, h), "normal", 0.02),
        "pooler.weight": ((h, h), "normal_low", 0.02),
        "pooler.bias": ((h,), "normal_low", 0.02),
        "transform.weight": ((h, h), "normal_low", 0.02),
        "transform.bias": ((h,), "normal_low", 0.02),
        "transform_ln.weight": ((h,), "ones", 0.02),
        "transform_ln.bias": ((h,), "normal", 0.02),
        "nsp.weight": ((h, 2), "normal_low", 0.02),
        "nsp.bias": ((2,), "normal_low", 0.02),
    }


def encode(params, cfg, ids, seg, mm):
    """-> ([B, S, H] last hidden states, [B, H] pooled [CLS])."""
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    act = cfg["hidden_act"]
    b, s = ids.shape
    x = params["word"][ids] + params["position"][:s] \
        + params["token_type"][seg]
    x = mm.act(C.layer_norm(x, params["emb_ln.weight"],
                            params["emb_ln.bias"], eps))
    blocks = {k[len("blocks."):]: w for k, w in params.items()
              if k.startswith("blocks.")}

    @jax.checkpoint
    def block(x, w):
        qkv = mm.dot(x, w["attn.qkv.weight"]) + w["attn.qkv.bias"]
        q, k, v = (t.reshape(b, s, heads, -1)
                   for t in jnp.split(mm.act(qkv), 3, axis=-1))
        a = C.attention(q, k, v, False, mm).reshape(b, s, -1)
        a = mm.dot(a, w["attn.proj.weight"]) + w["attn.proj.bias"]
        x = mm.act(C.layer_norm(x + a, w["ln1.weight"], w["ln1.bias"], eps))
        a = mm.act(C.gelu(mm.dot(x, w["fc1.weight"]) + w["fc1.bias"], act))
        a = mm.dot(a, w["fc2.weight"]) + w["fc2.bias"]
        x = mm.act(C.layer_norm(x + a, w["ln2.weight"], w["ln2.bias"], eps))
        return x, None

    x, _ = jax.lax.scan(block, x, blocks)
    pooled = jnp.tanh(mm.dot(x[:, 0], params["pooler.weight"])
                      + params["pooler.bias"])
    return x, pooled


def loss_rows(cfg, n_masked, n_rows, mm=None):
    """Masked-LM loss (mean over the batch's masked positions) plus
    next-sentence loss (mean over its rows) of a block of rows.
    ``mlm`` holds the label at a masked position and -100 elsewhere."""
    mm = mm or C.Matmul()
    eps, act = cfg["layer_norm_eps"], cfg["hidden_act"]

    def fn(params, ids, seg, mlm, nsp):
        x, pooled = encode(params, cfg, ids, seg, mm)
        t = C.gelu(mm.dot(x, params["transform.weight"])
                   + params["transform.bias"], act)
        t = mm.act(C.layer_norm(t, params["transform_ln.weight"],
                                params["transform_ln.bias"], eps))
        lg = mm.dot(t, params["word"].T).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        masked = mlm >= 0
        ll = jnp.take_along_axis(
            logp, jnp.where(masked, mlm, 0)[..., None], axis=-1)[..., 0]
        mlm_loss = -jnp.sum(jnp.where(masked, ll, 0.0)) / n_masked
        ns = mm.dot(pooled, params["nsp.weight"]) + params["nsp.bias"]
        ns = jax.nn.log_softmax(ns.astype(jnp.float32), axis=-1)
        nsp_loss = -jnp.sum(jnp.take_along_axis(
            ns, nsp[:, None], axis=-1)) / n_rows
        return mlm_loss + nsp_loss, [mlm_loss, nsp_loss]
    return fn


def train_loss_rows(cfg, batch, mm=None):
    """``loss_rows`` for a traffic file's ``batch``."""
    return loss_rows(cfg, batch["rows"] * batch["masked_per_row"],
                     batch["rows"], mm)
