"""GPT-2 as published (Radford et al. 2019; the released ``model.py``):
token + learned position embeddings, pre-LayerNorm blocks (attention
with one fused qkv projection, then a 4x GELU MLP), a final LayerNorm
and a head tied to the token embedding.

Leaves are stacked over the layers (``blocks.*`` have a leading layer
axis) so that the stack is one ``lax.scan``.  Matrices are [in, out];
the fused qkv projection's columns are q, k, v, each split into heads,
as in the release.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C


def table(cfg):
    """name -> (shape, kind, std) of every leaf."""
    h, n, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    f, p = cfg["n_inner"], cfg["n_positions"]
    out = 0.02 / math.sqrt(2 * n)
    return {
        "wte": ((v, h), "normal_low", 0.02),
        "wpe": ((p, h), "normal_low", 0.02),
        "ln_f.weight": ((h,), "ones", 0.02),
        "ln_f.bias": ((h,), "normal", 0.02),
        "blocks.ln1.weight": ((n, h), "ones", 0.02),
        "blocks.ln1.bias": ((n, h), "normal", 0.02),
        "blocks.attn.qkv.weight": ((n, h, 3 * h), "normal_low", 0.02),
        "blocks.attn.qkv.bias": ((n, 3 * h), "normal_low", 0.02),
        "blocks.attn.proj.weight": ((n, h, h), "normal_low", out),
        "blocks.attn.proj.bias": ((n, h), "normal_low", 0.02),
        "blocks.ln2.weight": ((n, h), "ones", 0.02),
        "blocks.ln2.bias": ((n, h), "normal", 0.02),
        "blocks.mlp.fc1.weight": ((n, h, f), "normal_low", 0.02),
        "blocks.mlp.fc1.bias": ((n, f), "normal_low", 0.02),
        "blocks.mlp.fc2.weight": ((n, f, h), "normal_low", out),
        "blocks.mlp.fc2.bias": ((n, h), "normal_low", 0.02),
    }


def hidden(params, cfg, ids, mm):
    """[B, S] token ids -> [B, S, H] after the final LayerNorm."""
    eps, heads = cfg["layer_norm_epsilon"], cfg["n_head"]
    b, s = ids.shape
    x = mm.act(params["wte"][ids] + params["wpe"][:s])
    blocks = {k[len("blocks."):]: w for k, w in params.items()
              if k.startswith("blocks.")}

    @jax.checkpoint
    def block(x, w):
        a = mm.act(C.layer_norm(x, w["ln1.weight"], w["ln1.bias"], eps))
        qkv = mm.dot(a, w["attn.qkv.weight"]) + w["attn.qkv.bias"]
        q, k, v = (t.reshape(b, s, heads, -1)
                   for t in jnp.split(mm.act(qkv), 3, axis=-1))
        a = C.attention(q, k, v, True, mm).reshape(b, s, -1)
        x = mm.act(x + mm.dot(a, w["attn.proj.weight"])
                   + w["attn.proj.bias"])
        a = mm.act(C.layer_norm(x, w["ln2.weight"], w["ln2.bias"], eps))
        a = mm.act(C.gelu(mm.dot(a, w["mlp.fc1.weight"])
                          + w["mlp.fc1.bias"], cfg["activation_function"]))
        x = mm.act(x + mm.dot(a, w["mlp.fc2.weight"]) + w["mlp.fc2.bias"])
        return x, None

    x, _ = jax.lax.scan(block, x, blocks)
    return mm.act(C.layer_norm(x, params["ln_f.weight"],
                               params["ln_f.bias"], eps))


def logits(params, cfg, ids, mm=None):
    mm = mm or C.Matmul()
    return mm.dot(hidden(params, cfg, ids, mm), params["wte"].T)


def loss_rows(cfg, n_tokens, mm=None):
    """The causal-LM loss of a block of rows, divided by the batch's
    token count: ``labels`` are the next tokens, as the data pipeline
    shifts them."""
    mm = mm or C.Matmul()

    def fn(params, ids, labels):
        lg = logits(params, cfg, ids, mm).astype(jnp.float32)
        logp = jax.nn.log_softmax(lg, axis=-1)
        ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)
        total = -jnp.sum(ll) / n_tokens
        return total, [total]
    return fn


def train_loss_rows(cfg, batch, mm=None):
    """``loss_rows`` for a traffic file's ``batch``."""
    return loss_rows(cfg, batch["rows"] * batch["seq_len"], mm)
