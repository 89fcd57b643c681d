"""Autoregressive generation with KV caches.

Capability analog of the reference ecosystem's ``model.generate`` (greedy /
temperature / nucleus sampling; the reference keeps generation in PaddleNLP
but ships the primitives in-tree: ``top_p_sampling``, block/paged KV
attention kernels — SURVEY C12). TPU-shaped: the decode step is ONE jitted
program with static shapes — caches are preallocated [B, max_len, Hkv, D]
and updated in place with ``dynamic_update_slice`` at the traced position;
attention masks positions beyond the current length. The per-token Python
loop re-invokes the same compiled step (functional cache threading — no
retrace after the first token).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.dispatch import primitive
from ..core.tensor import Tensor


@primitive
def cache_attention(q, k_new, v_new, k_cache, v_cache, pos,
                    scale=None):
    """One decode step of attention against a preallocated KV cache.

    q/k_new/v_new: [B, 1, H(q|kv), D]; caches [B, L, Hkv, D]; pos [1]
    (traced). Returns (out [B, 1, Hq, D], k_cache', v_cache'). GQA: kv
    heads repeat to match q heads. Positions > pos are masked out.
    """
    p = pos.reshape(())
    kc = lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(
        k_cache.dtype), p, axis=1)
    vc = lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(
        v_cache.dtype), p, axis=1)
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    hq, hk = q.shape[2], kc.shape[2]
    kt, vt = kc, vc
    if hk != hq:
        kt = jnp.repeat(kt, hq // hk, axis=2)
        vt = jnp.repeat(vt, hq // hk, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kt,
                        preferred_element_type=jnp.float32) * s
    valid = (jnp.arange(kc.shape[1]) <= p)[None, None, None, :]
    logits = jnp.where(valid, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vt)
    return out, kc, vc


@primitive
def paged_cache_attention(q, k_new, v_new, k_pages, v_pages, pos,
                          block_tables=None, scale=None):
    """One decode step against a PAGED KV cache (the reference's
    ``block_multi_head_attention`` capability — SURVEY C12).

    q/k_new/v_new: [B, 1, H(q|kv), D]; page pools [Hkv, P, page_size, D];
    ``block_tables`` (static attr) [B, pages_per_seq] page ids; pos [1]
    traced. Appends the new token into its (page, slot) and attends over
    the pages via the Pallas paged-decode kernel (attention cost scales
    with the current length, not max_len).
    """
    from ..ops.pallas.paged_attention import paged_decode_attention

    p = pos.reshape(())
    bt = jnp.asarray(np.asarray(block_tables), jnp.int32)   # [B, NP]
    b = q.shape[0]
    ps = k_pages.shape[2]
    page = bt[jnp.arange(b), p // ps]                       # [B]
    slot = p % ps
    kn = jnp.swapaxes(k_new[:, 0], 0, 1).astype(k_pages.dtype)  # [Hk, B, D]
    vn = jnp.swapaxes(v_new[:, 0], 0, 1).astype(v_pages.dtype)
    k_pages = k_pages.at[:, page, slot].set(kn)
    v_pages = v_pages.at[:, page, slot].set(vn)
    seq_lens = jnp.full((b,), p + 1, jnp.int32)
    out = paged_decode_attention(q[:, 0], k_pages, v_pages, bt, seq_lens,
                                 scale=scale)
    return out[:, None].astype(q.dtype), k_pages, v_pages


def _slot_page_write(kn, vn, k_pages, v_pages, bt, positions,
                     k_scales=None, v_scales=None):
    """Write one token per slot into its (page, slot): the ONE home of
    the per-slot page-write discipline — :func:`paged_slot_attention`
    AND the tensor-parallel decode path (``_tp_attend_decode``) both
    write through here, so the 'identical bytes' invariants (prefix
    cache, preempt-requeue, TP-replicated GQA pools) cannot drift
    between them.  ``kn``/``vn`` are head-major ``[Hk, B, D]``;
    scales switch on the int8 quantize-on-write path."""
    from ..quantization import kv_quantize

    p = positions.reshape(-1).astype(jnp.int32)             # [B]
    b = p.shape[0]
    ps = k_pages.shape[2]
    page = bt[jnp.arange(b), jnp.minimum(p // ps, bt.shape[1] - 1)]
    slot = p % ps
    if k_scales is not None:
        kn, k_sc = kv_quantize(kn)
        vn, v_sc = kv_quantize(vn)
        k_scales = k_scales.at[:, page, slot].set(k_sc)
        v_scales = v_scales.at[:, page, slot].set(v_sc)
    k_pages = k_pages.at[:, page, slot].set(kn.astype(k_pages.dtype))
    v_pages = v_pages.at[:, page, slot].set(vn.astype(v_pages.dtype))
    return k_pages, v_pages, k_scales, v_scales


def _ragged_page_write(kn, vn, k_pages, v_pages, bt, tok_pos, tok_slot,
                       tok_valid, k_scales=None, v_scales=None):
    """Packed-token analog of :func:`_slot_page_write` (invalid tokens
    route to the reserved null page 0) — shared by
    :func:`ragged_paged_step` and the TP ragged path
    (``_tp_attend_ragged``)."""
    from ..quantization import kv_quantize

    ps = k_pages.shape[2]
    pos = tok_pos.astype(jnp.int32)
    sl = tok_slot.astype(jnp.int32)
    ok = tok_valid.astype(jnp.bool_)
    page = jnp.where(
        ok, bt[sl, jnp.minimum(pos // ps, bt.shape[1] - 1)], 0)
    wslot = jnp.where(ok, pos % ps, 0)
    if k_scales is not None:
        kn, k_sc = kv_quantize(kn)
        vn, v_sc = kv_quantize(vn)
        k_scales = k_scales.at[:, page, wslot].set(k_sc)
        v_scales = v_scales.at[:, page, wslot].set(v_sc)
    k_pages = k_pages.at[:, page, wslot].set(kn.astype(k_pages.dtype))
    v_pages = v_pages.at[:, page, wslot].set(vn.astype(v_pages.dtype))
    return k_pages, v_pages, k_scales, v_scales


@primitive
def paged_slot_attention(q, k_new, v_new, k_pages, v_pages, positions,
                         block_tables, scale=None, pages_per_block=None,
                         k_scales=None, v_scales=None):
    """One decode step against a paged KV cache with PER-SLOT state —
    the continuous-batching variant of :func:`paged_cache_attention`.

    Unlike the static-attribute form, ``positions`` [B] (each slot's
    current token index) and ``block_tables`` [B, NP] are TRACED
    tensors: the serving engine admits/retires requests by changing
    their VALUES between dispatches, never recompiling.  Writes each
    slot's new K/V at its own (page, slot) and attends through the
    ragged Pallas kernel with per-slot lengths.

    ``k_scales``/``v_scales`` [Hk, P, page_size] switch on the int8 KV
    path: the new K/V quantize on write (``quantization.kv_quantize``,
    one absmax scale per head per token slot — path-independent bytes),
    the kernel dequantizes the fetched pages, and the updated scale pools
    return alongside the data pools.
    """
    from ..ops.pallas.paged_attention import paged_decode_attention

    if (k_scales is None) != (v_scales is None):
        raise ValueError("paged_slot_attention: pass both k_scales "
                         "and v_scales or neither")
    quant = k_scales is not None
    p = positions.reshape(-1).astype(jnp.int32)             # [B]
    bt = block_tables.astype(jnp.int32)
    kn = jnp.swapaxes(k_new[:, 0], 0, 1)                    # [Hk, B, D]
    vn = jnp.swapaxes(v_new[:, 0], 0, 1)
    k_pages, v_pages, k_scales, v_scales = _slot_page_write(
        kn, vn, k_pages, v_pages, bt, positions, k_scales, v_scales)
    out = paged_decode_attention(q[:, 0], k_pages, v_pages, bt, p + 1,
                                 scale=scale,
                                 pages_per_block=pages_per_block,
                                 k_scales=k_scales, v_scales=v_scales)
    out = out[:, None].astype(q.dtype)
    if quant:
        return out, k_pages, v_pages, k_scales, v_scales
    return out, k_pages, v_pages


@primitive
def ragged_paged_step(q, k_new, v_new, k_pages, v_pages, tok_pos,
                      tok_slot, tok_valid, kv_lens, q_lens, block_tables,
                      scale=None, q_block=8, pages_per_block=None,
                      k_scales=None, v_scales=None):
    """Attention for ONE continuously-batched step over packed tokens.

    q/k_new/v_new: [T, H(q|kv), D] — tokens of all sequences packed in
    slot order (each slot's segment padded to a ``q_block`` multiple);
    tok_pos/tok_slot/tok_valid: [T] per-token absolute position, owning
    slot, and validity (padding tokens route their K/V write to the
    engine's reserved null page 0); kv_lens/q_lens: [B] per-slot totals
    (kv INCLUDING this step's tokens).  Prefill chunks and single-token
    decodes share this one call — the kernel's per-sequence causal
    offset handles both.

    ``k_scales``/``v_scales`` [Hk, P, page_size] switch on the int8 KV
    path (ISSUE 7): this step's K/V quantize ON WRITE at page-slot
    granularity (``quantization.kv_quantize`` — each token's bytes are
    a pure function of its own K/V vector, so a page filled by prefill
    chunks or token-by-token decode holds identical bytes and prefix-
    cache reuse stays exact), the scale vectors land in side-pools
    indexed by the same block tables, and the ragged kernel dequantizes
    on the fetched pages.  The updated scale pools return after the data
    pools.
    """
    from ..ops.pallas.paged_attention import ragged_paged_attention

    if (k_scales is None) != (v_scales is None):
        raise ValueError("ragged_paged_step: pass both k_scales "
                         "and v_scales or neither")
    quant = k_scales is not None
    bt = block_tables.astype(jnp.int32)
    kn = jnp.swapaxes(k_new, 0, 1)                          # [Hk, T, D]
    vn = jnp.swapaxes(v_new, 0, 1)
    k_pages, v_pages, k_scales, v_scales = _ragged_page_write(
        kn, vn, k_pages, v_pages, bt, tok_pos, tok_slot, tok_valid,
        k_scales, v_scales)
    out = ragged_paged_attention(q, k_pages, v_pages, bt,
                                 kv_lens.astype(jnp.int32),
                                 q_lens.astype(jnp.int32),
                                 q_block=q_block, scale=scale,
                                 pages_per_block=pages_per_block,
                                 k_scales=k_scales, v_scales=v_scales)
    out = out.astype(q.dtype)
    if quant:
        return out, k_pages, v_pages, k_scales, v_scales
    return out, k_pages, v_pages


@primitive
def guarded_argmax(lg, poison):
    """Greedy token pick with a device-side finite-ness flag — the
    serving decode guard's in-graph half (``resilience.serving``).
    (``guarded_argmax.raw`` is the jnp-level form the decode-window
    scan body uses.)

    ``lg`` [B, V] logits, ``poison`` [B] float32 (0.0 normally, NaN for
    a slot the ``engine_nan_decode`` drill poisons). Returns
    ``(nxt [B] int32, bad [B] bool)``. Adding 0.0f to finite logits is
    argmax-invariant (the lone effect, -0.0 -> +0.0, compares equal),
    so token streams are unchanged when the guard is idle; a bad row's
    token is forced to 0 so the engine's host replay sees a
    deterministic (discarded) value instead of argmax-over-NaN.

    Runs INSIDE the engine's compiled mixed/decode programs and rides
    the decode-window scan carry: detection of a non-finite request —
    whatever layer the NaN entered at, since rows only mix within a
    slot on the ``ragged_paged_step`` path — costs no extra host sync.
    """
    lg = lg.astype(jnp.float32) + poison.reshape(-1)[:, None]
    bad = jnp.logical_not(jnp.all(jnp.isfinite(lg), axis=-1))
    nxt = jnp.where(bad, 0, lg.argmax(-1)).astype(jnp.int32)
    return nxt, bad


@primitive
def verify_argmax(lg, tok_slot, tok_valid, poison):
    """Per-ROW greedy pick + per-slot finiteness flag — the ragged
    VERIFY entry of the speculative decoding subsystem (ISSUE 9;
    ``inference/speculative.py``).

    Where :func:`guarded_argmax` serves one gathered row per slot, the
    speculative mixed program needs the target's greedy token after
    EVERY packed position: a slot's verify segment (current token + K
    drafts, ``q_lens = K+1``) yields K+1 candidate tokens, and the host
    accepts the longest prefix whose drafts agree — the variable
    per-slot advance that multiplies tokens per dispatch.

    ``lg`` [T, V] packed logits, ``tok_slot``/``tok_valid`` [T] the
    packing vectors, ``poison`` [B] float32 (0.0 normally; NaN for a
    slot the ``engine_nan_decode``/``engine_draft_nan`` drills poison —
    broadcast to the slot's rows, argmax-invariant when 0).  Returns
    ``(toks [T] int32, bad [B] bool)``: ``bad`` is the PER-DRAFT guard
    — ANY non-finite valid row fails its slot alone (padding rows are
    masked; their logits are garbage by contract), and a bad row's
    token is forced to 0 so the host replay sees a deterministic
    discarded value."""
    sl = tok_slot.reshape(-1).astype(jnp.int32)
    pv = poison.reshape(-1)
    lg = lg.astype(jnp.float32) + pv[sl][:, None]
    valid = tok_valid.reshape(-1).astype(jnp.bool_)
    row_bad = jnp.logical_not(jnp.all(jnp.isfinite(lg), axis=-1)) \
        & valid
    toks = jnp.where(row_bad, 0, lg.argmax(-1)).astype(jnp.int32)
    bad = jnp.zeros(pv.shape[0], jnp.int32).at[sl].max(
        row_bad.astype(jnp.int32)) > 0
    return toks, bad


@primitive
def cache_prefill(k_new, v_new, k_cache, v_cache):
    """Write the WHOLE prompt's K/V [B, S, Hkv, D] into cache[:, :S] in
    one shot (batched prefill — the serving-path complement of the
    per-token ``cache_attention``; the reference reaches this via its
    fused multi-transformer prefill kernels)."""
    kc = lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), 0, axis=1)
    vc = lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), 0, axis=1)
    return kc, vc


@primitive
def paged_cache_prefill(k_new, v_new, k_pages, v_pages,
                        block_tables=None):
    """Scatter the prompt's K/V [B, S, Hkv, D] into the page pools at
    (page, slot) = (bt[b, t//ps], t%ps) for t in [0, S)."""
    b, s, hk, d = k_new.shape
    bt = jnp.asarray(np.asarray(block_tables), jnp.int32)
    ps = k_pages.shape[2]
    t = jnp.arange(s)
    page = bt[:, t // ps]                        # [B, S]
    slot = jnp.broadcast_to(t % ps, (b, s))      # [B, S]
    kn = jnp.transpose(k_new, (2, 0, 1, 3)).astype(k_pages.dtype)
    vn = jnp.transpose(v_new, (2, 0, 1, 3)).astype(v_pages.dtype)
    k_pages = k_pages.at[:, page, slot].set(kn)
    v_pages = v_pages.at[:, page, slot].set(vn)
    return k_pages, v_pages


def _apply_rope(x, cos, sin):
    """Rotate-half application — the ONE body both rope primitives share
    (llama.rope_angles is the one home of the angle convention)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


@primitive
def rope_at(x, pos, theta=10000.0):
    """Half-rotation rope at explicit positions (decode / serving).
    Convention comes from llama.rope_angles (single home — training and
    decode paths cannot drift).  Three position shapes:

    * pos [1] (classic decode): one traced position for the whole batch;
    * pos [B] matching x [B, 1, H, D]: per-slot positions (the
      continuous-batching decode step — every slot is at its own depth);
    * pos [T] matching x [1, T, H, D]: per-token positions (the packed
      ragged prefill+decode step).
    """
    from .llama import rope_angles
    p = pos.reshape(-1)
    n = p.shape[0]
    cos, sin = rope_angles(p, x.shape[-1], theta)        # [n, D]
    if n == 1:
        cos, sin = cos.reshape(-1), sin.reshape(-1)      # broadcast all
    elif n == x.shape[0]:
        cos, sin = cos[:, None, None, :], sin[:, None, None, :]
    elif n == x.shape[1]:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        raise ValueError(
            f"rope_at: {n} positions do not match x {x.shape}")
    return _apply_rope(x, cos, sin)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _zero_pool(shape, count, dtype="float32"):
    """``count`` zeroed arrays of ``shape`` in ONE device launch (jit's
    static-arg cache keeps one compiled program per geometry): a
    12-layer KV pool as 24 separate ``jnp.zeros`` dispatches pays 24
    launches of per-request latency over a network-attached chip.
    ``dtype`` (static string) lets the quantized serving engine build
    int8 data pools and f32 scale pools through the same program
    cache."""
    return tuple(jnp.zeros(shape, jnp.dtype(dtype))
                 for _ in range(count))


def make_import_scatter(n_pools, out_shardings=None):
    """The KV-page import scatter program (PR13 handoff, reused by
    ISSUE 20 live-migration restore): ONE donated jit per pool
    geometry that writes a payload's page rows into the pool pages
    named by ``idx``.  The page-id vector is traced DATA (padded to
    the block-table width by the caller), so every import/restore of
    a geometry rides the same compiled program; donation keeps the
    update in-place in HBM.  ``out_shardings`` pins the TP kv-head
    sharding when the pools live on a mesh."""
    def imp(idx, *args):
        pools, payload = args[:n_pools], args[n_pools:]
        return tuple(p.at[:, idx].set(pl.astype(p.dtype))
                     for p, pl in zip(pools, payload))

    kw = {} if out_shardings is None else {
        "out_shardings": tuple(out_shardings)}
    return jax.jit(imp, donate_argnums=tuple(range(1, 1 + n_pools)),
                   **kw)


def _split_caches(caches, n_layers):
    """Serving cache-list layout: ``[k0, v0, ..., kL-1, vL-1]`` for fp
    pools, with the int8 path APPENDING the per-page scale side-pools
    ``[ks0, vs0, ..., ksL-1, vsL-1]`` (``inference/engine.py`` builds
    the list; the length is self-describing).  Returns
    ``(data, scales)`` with ``scales == []`` on the fp path — the ONE
    place the decode/ragged forwards learn whether KV is quantized."""
    n = 2 * n_layers
    if len(caches) == 2 * n:
        return caches[:n], caches[n:]
    if len(caches) != n:
        raise ValueError(
            f"expected {n} (fp) or {2 * n} (int8 + scales) cache pools "
            f"for {n_layers} layers, got {len(caches)}")
    return caches, []


def _empty_caches(model, batch, max_len):
    cfg = model.cfg
    n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
    shape = (batch, max_len, n_kv, cfg.head_dim)
    return [Tensor(a) for a in _zero_pool(shape, 2 * cfg.num_layers)]


def _attend_layer(attend, q, k, v, data, scales, li, pos):
    """One layer's cache update + attention, fp or int8: returns
    ``(att, new_data_pair, new_scale_pair)``.  The quantized call adds
    the layer's scale pools and gets them back updated."""
    kc, vc = data[2 * li], data[2 * li + 1]
    if scales:
        ks, vs = scales[2 * li], scales[2 * li + 1]
        att, kc, vc, ks, vs = attend(q, k, v, kc, vc, pos, ks, vs)
        return att, [kc, vc], [ks, vs]
    att, kc, vc = attend(q, k, v, kc, vc, pos)
    return att, [kc, vc], []


def _gpt_decode(model, ids_t, pos, caches, attend=cache_attention):
    """One-token logits for GPTForCausalLM given flat [k0,v0,k1,v1,...]
    caches (int8 serving appends scale pools — ``_split_caches``);
    returns (logits [B, V], new caches). ``pos`` may be [1]
    (one shared position) or [B] (per-slot positions — the serving
    engine's continuously-batched decode)."""
    from .. import ops
    gpt = model.gpt
    data, scales = _split_caches(caches, len(gpt.blocks))
    x = gpt.wte(ids_t) + gpt.wpe(ops.reshape(pos, [-1, 1]))
    new, new_sc = [], []
    for li, blk in enumerate(gpt.blocks):
        h = blk.ln1(x)
        b, s, hidden = h.shape
        qkv = ops.reshape(blk.attn.qkv(h),
                          [b, 1, 3, blk.attn.num_heads,
                           blk.attn.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)
        att, pair, sc_pair = _attend_layer(attend, q, k, v, data,
                                           scales, li, pos)
        x = x + blk.attn.proj(ops.reshape(att, [b, 1, hidden]))
        x = x + blk.mlp(blk.ln2(x))
        new.extend(pair)
        new_sc.extend(sc_pair)
    h = gpt.ln_f(x)
    if model.lm_head is not None:
        logits = model.lm_head(h)
    else:
        logits = ops.matmul(h, gpt.wte.weight, transpose_y=True)
    return ops.reshape(logits, [logits.shape[0], -1]), new + new_sc


def _llama_decode(model, ids_t, pos, caches, attend=cache_attention):
    from .. import ops
    lm = model.llama
    data, scales = _split_caches(caches, len(lm.layers))
    x = lm.embed_tokens(ids_t)
    new, new_sc = [], []
    for li, layer in enumerate(lm.layers):
        att_in = layer.input_norm(x)
        a = layer.attn
        b = att_in.shape[0]
        q = ops.reshape(a.q_proj(att_in), [b, 1, a.num_heads, a.head_dim])
        k = ops.reshape(a.k_proj(att_in),
                        [b, 1, a.num_kv_heads, a.head_dim])
        v = ops.reshape(a.v_proj(att_in),
                        [b, 1, a.num_kv_heads, a.head_dim])
        q = rope_at(q, pos, theta=a.rope_theta)
        k = rope_at(k, pos, theta=a.rope_theta)
        att, pair, sc_pair = _attend_layer(attend, q, k, v, data,
                                           scales, li, pos)
        x = x + a.o_proj(ops.reshape(att, [b, 1, -1]))
        x = x + layer.mlp(layer.post_norm(x))
        new.extend(pair)
        new_sc.extend(sc_pair)
    h = lm.norm(x)
    if model.lm_head is not None:
        logits = model.lm_head(h)
    else:
        logits = ops.matmul(h, lm.embed_tokens.weight, transpose_y=True)
    return ops.reshape(logits, [logits.shape[0], -1]), new + new_sc


def _ragged_attend_layer(q, k, v, data, scales, li, tok_pos, tok_slot,
                         tok_valid, kv_lens, q_lens, bt, q_block,
                         pages_per_block):
    """One layer's packed-token page write + ragged attention, fp or
    int8 (the :func:`_attend_layer` analog for the mixed serving step):
    returns ``(att, new_data_pair, new_scale_pair)``."""
    kc, vc = data[2 * li], data[2 * li + 1]
    if scales:
        att, kc, vc, ks, vs = ragged_paged_step(
            q, k, v, kc, vc, tok_pos, tok_slot, tok_valid, kv_lens,
            q_lens, bt, q_block=q_block,
            pages_per_block=pages_per_block,
            k_scales=scales[2 * li], v_scales=scales[2 * li + 1])
        return att, [kc, vc], [ks, vs]
    att, kc, vc = ragged_paged_step(
        q, k, v, kc, vc, tok_pos, tok_slot, tok_valid, kv_lens,
        q_lens, bt, q_block=q_block, pages_per_block=pages_per_block)
    return att, [kc, vc], []


def _gpt_ragged_forward(model, ids_t, tok_pos, tok_slot, tok_valid,
                        kv_lens, q_lens, bt, caches, q_block,
                        pages_per_block=None):
    """Packed-token forward for a continuously-batched serving step:
    ``ids_t`` [1, T] carries prefill chunks AND single decode tokens of
    all slots (segments in slot order, ``q_block``-padded); per-token
    position/slot/validity vectors drive the page writes and the ragged
    attention.  Returns ([T, V] logits — padding rows garbage — and the
    new page pools)."""
    from .. import ops
    gpt = model.gpt
    data, scales = _split_caches(caches, len(gpt.blocks))
    t = ids_t.shape[1]
    x = gpt.wte(ids_t) + gpt.wpe(ops.reshape(tok_pos, [1, -1]))
    new, new_sc = [], []
    for li, blk in enumerate(gpt.blocks):
        h = blk.ln1(x)
        hd, nh = blk.attn.head_dim, blk.attn.num_heads
        qkv = ops.reshape(blk.attn.qkv(h), [t, 3, nh, hd])
        q, k, v = ops.unbind(qkv, axis=1)                  # [T, nh, hd]
        att, pair, sc_pair = _ragged_attend_layer(
            q, k, v, data, scales, li, tok_pos, tok_slot, tok_valid,
            kv_lens, q_lens, bt, q_block, pages_per_block)
        x = x + blk.attn.proj(ops.reshape(att, [1, t, nh * hd]))
        x = x + blk.mlp(blk.ln2(x))
        new.extend(pair)
        new_sc.extend(sc_pair)
    h = gpt.ln_f(x)
    if model.lm_head is not None:
        logits = model.lm_head(h)
    else:
        logits = ops.matmul(h, gpt.wte.weight, transpose_y=True)
    return ops.reshape(logits, [t, -1]), new + new_sc


def _llama_ragged_forward(model, ids_t, tok_pos, tok_slot, tok_valid,
                          kv_lens, q_lens, bt, caches, q_block,
                          pages_per_block=None):
    from .. import ops
    lm = model.llama
    data, scales = _split_caches(caches, len(lm.layers))
    t = ids_t.shape[1]
    x = lm.embed_tokens(ids_t)
    new, new_sc = [], []
    for li, layer in enumerate(lm.layers):
        att_in = layer.input_norm(x)
        a = layer.attn
        q = ops.reshape(a.q_proj(att_in), [1, t, a.num_heads, a.head_dim])
        k = ops.reshape(a.k_proj(att_in),
                        [1, t, a.num_kv_heads, a.head_dim])
        v = ops.reshape(a.v_proj(att_in),
                        [1, t, a.num_kv_heads, a.head_dim])
        q = rope_at(q, tok_pos, theta=a.rope_theta)
        k = rope_at(k, tok_pos, theta=a.rope_theta)
        att, pair, sc_pair = _ragged_attend_layer(
            ops.reshape(q, [t, a.num_heads, a.head_dim]),
            ops.reshape(k, [t, a.num_kv_heads, a.head_dim]),
            ops.reshape(v, [t, a.num_kv_heads, a.head_dim]),
            data, scales, li, tok_pos, tok_slot, tok_valid,
            kv_lens, q_lens, bt, q_block, pages_per_block)
        x = x + a.o_proj(ops.reshape(att, [1, t, -1]))
        x = x + layer.mlp(layer.post_norm(x))
        new.extend(pair)
        new_sc.extend(sc_pair)
    h = lm.norm(x)
    if model.lm_head is not None:
        logits = model.lm_head(h)
    else:
        logits = ops.matmul(h, lm.embed_tokens.weight, transpose_y=True)
    return ops.reshape(logits, [t, -1]), new + new_sc


def _ragged_fn(model):
    """Family dispatch for the packed continuous-batching forward."""
    from .gpt import GPTForCausalLM
    from .llama import LlamaForCausalLM
    if isinstance(model, GPTForCausalLM):
        return _gpt_ragged_forward
    if isinstance(model, LlamaForCausalLM):
        return _llama_ragged_forward
    raise TypeError(
        f"serving engine: unsupported model {type(model).__name__}")


@primitive
def rope_span(x, theta=10000.0):
    """Half-rotation rope over positions 0..S-1 for the prefill pass:
    x [B, S, H, D]. Angles/application share the rope_at homes (f64
    tables like the training path — the decode path's traced-f32 angles
    differ in low-order bits, the same tolerance the cached-vs-full
    parity test already covers)."""
    from .llama import rope_angles
    cos, sin = rope_angles(np.arange(x.shape[1]), x.shape[-1], theta)
    return _apply_rope(x, jnp.asarray(cos)[None, :, None, :],
                       jnp.asarray(sin)[None, :, None, :])


def _prompt_attention(q, k, v, use_flash=True):
    import paddle_tpu.nn.functional as F
    return F.scaled_dot_product_attention(
        q, k, v, is_causal=True, dropout_p=0.0,
        backend=None if use_flash else "xla")


def _gpt_prefill(model, ids, caches, write):
    """Whole-prompt forward that fills the KV caches and returns the
    LAST position's logits — one compiled pass instead of S decode
    steps (the serving prefill/decode split)."""
    from .. import ops
    gpt = model.gpt
    b, s = ids.shape
    x = gpt.wte(ids) + gpt.wpe(ops.arange(0, s, dtype="int32"))
    new = []
    for li, blk in enumerate(gpt.blocks):
        h = blk.ln1(x)
        qkv = ops.reshape(blk.attn.qkv(h),
                          [b, s, 3, blk.attn.num_heads, blk.attn.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)
        kc, vc = write(k, v, caches[2 * li], caches[2 * li + 1])
        att = _prompt_attention(q, k, v, blk.attn.use_flash)
        x = x + blk.attn.proj(ops.reshape(att, [b, s, -1]))
        x = x + blk.mlp(blk.ln2(x))
        new.extend([kc, vc])
    h = gpt.ln_f(x)
    last = h[:, s - 1:s]
    if model.lm_head is not None:
        logits = model.lm_head(last)
    else:
        logits = ops.matmul(last, gpt.wte.weight, transpose_y=True)
    return ops.reshape(logits, [b, -1]), new


def _llama_prefill(model, ids, caches, write):
    from .. import ops
    lm = model.llama
    b, s = ids.shape
    x = lm.embed_tokens(ids)
    new = []
    for li, layer in enumerate(lm.layers):
        att_in = layer.input_norm(x)
        a = layer.attn
        q = ops.reshape(a.q_proj(att_in), [b, s, a.num_heads, a.head_dim])
        k = ops.reshape(a.k_proj(att_in),
                        [b, s, a.num_kv_heads, a.head_dim])
        v = ops.reshape(a.v_proj(att_in),
                        [b, s, a.num_kv_heads, a.head_dim])
        q = rope_span(q, theta=a.rope_theta)
        k = rope_span(k, theta=a.rope_theta)
        kc, vc = write(k, v, caches[2 * li], caches[2 * li + 1])
        att = _prompt_attention(q, k, v,
                                model.cfg.use_flash_attention)
        x = x + a.o_proj(ops.reshape(att, [b, s, -1]))
        x = x + layer.mlp(layer.post_norm(x))
        new.extend([kc, vc])
    h = lm.norm(x)
    last = h[:, s - 1:s]
    if model.lm_head is not None:
        logits = model.lm_head(last)
    else:
        logits = ops.matmul(last, lm.embed_tokens.weight, transpose_y=True)
    return ops.reshape(logits, [b, -1]), new


def _decode_fn(model):
    """(decode_fn, prefill_fn, hard_position_limit): GPT's learned wpe
    table makes max_seq_len a hard bound; LLaMA's rope extrapolates."""
    from .gpt import GPTForCausalLM
    from .llama import LlamaForCausalLM
    if isinstance(model, GPTForCausalLM):
        return _gpt_decode, _gpt_prefill, True
    if isinstance(model, LlamaForCausalLM):
        return _llama_decode, _llama_prefill, False
    raise TypeError(f"generate: unsupported model {type(model).__name__}")


def _empty_paged_caches(model, batch, max_len, page_size):
    """Per-layer page pools [Hkv, B * pages_per_seq, page_size, D] plus the
    static block table (sequence b owns pages [b*NP, (b+1)*NP) — the
    deterministic allocation of uniform batched decode; a serving-style
    allocator would supply its own table)."""
    cfg = model.cfg
    n_kv = getattr(cfg, "num_kv_heads", cfg.num_heads)
    np_per_seq = -(-max_len // page_size)
    bt = np.arange(batch * np_per_seq, dtype=np.int32).reshape(
        batch, np_per_seq)
    shape = (n_kv, batch * np_per_seq, page_size, cfg.head_dim)
    caches = [Tensor(a) for a in _zero_pool(shape, 2 * cfg.num_layers)]
    return caches, bt


def _make_decode_window(exe, K, temperature, top_p, has_eos):
    """Fold K decode steps of a compiled step into ONE program: forward,
    sampling and the eos mask all run on device; the sampled token feeds
    back through the scan carry. One host dispatch per K tokens instead
    of per token — the serving analog of ``jit.multi_step``."""
    from jax import lax

    pure = exe._pure
    n_ret = exe.n_ret                      # logits + caches
    n_caches = n_ret - 1
    capt = exe.capt_state
    carry_idx, const_idx = exe.state_split()
    greedy = (top_p is None and temperature == 1.0)

    def window(tok, pos, caches, cstate, const_state, finished, eos_id,
               key):
        def body(c, _):
            tok, pos, caches, cstate, fin, key = c
            state = [None] * len(capt)
            for i, v in zip(carry_idx, cstate):
                state[i] = v
            for i, v in zip(const_idx, const_state):
                state[i] = v
            outs = pure(tok, pos, *caches, *state)
            lg = outs[0].astype(jnp.float32)
            new_caches = list(outs[1:1 + n_caches])
            new_cstate = list(outs[1 + n_caches:
                                   1 + n_caches + len(carry_idx)])
            if greedy:
                nxt = lg.argmax(-1).astype(jnp.int32)
            else:
                key, sub = jax.random.split(key)
                lg = lg / max(float(temperature), 1e-6)
                if top_p is not None:
                    from ..ops.special import nucleus_sample_jnp
                    p = jnp.full((lg.shape[0],), float(top_p),
                                 jnp.float32)
                    _, tok2d = nucleus_sample_jnp(sub, lg, p)
                    nxt = tok2d[:, 0].astype(jnp.int32)
                else:
                    nxt = jax.random.categorical(
                        sub, lg, axis=-1).astype(jnp.int32)
            if has_eos:
                nxt = jnp.where(fin, eos_id, nxt)
                fin = fin | (nxt == eos_id)
            return (nxt[:, None], pos + 1, new_caches, new_cstate, fin,
                    key), nxt

        (tok, pos, caches, cstate, fin, key), toks = lax.scan(
            body, (tok, pos, caches, cstate, finished, key), None,
            length=K)
        return toks, tok, pos, caches, cstate, fin, key

    return jax.jit(window, donate_argnums=(2, 3))


def generate(model, input_ids, max_new_tokens=32, temperature=1.0,
             top_p=None, eos_token_id=None, seed=None, use_jit=True,
             kv_cache="dense", page_size=16, prefill=True,
             decode_window=None):
    """Greedy / temperature / nucleus decoding with a KV cache.

    ``input_ids`` [B, S] prompt; returns [B, S + max_new_tokens] int32
    (rows stop changing after ``eos_token_id``). One compiled decode step
    serves both prefill and generation (same static shapes).

    ``kv_cache="paged"`` stores KV in a page pool with per-sequence block
    tables and attends through the Pallas paged-decode kernel (the
    reference's ``block_multi_head_attention`` serving path): attention
    compute scales with the current length instead of ``max_len``, the
    win at long sequences.

    ``prefill=True`` (default) processes the whole prompt in ONE compiled
    forward that fills the KV caches — prompt cost is a single pass
    instead of prompt_len decode steps (the serving prefill/decode
    split). ``prefill=False`` keeps the pure token-by-token path.

    ``decode_window``: scan K decode steps (forward + sampling + eos
    masking, all on device) into ONE dispatch — over a network-attached
    chip the wall time per token drops ~K-fold. Defaults to 8 for greedy
    decoding; sampling paths default to 1 because the windowed sampler
    draws from the device RNG stream (a different, equally-seeded stream
    than the host path) — pass decode_window>1 to opt in.
    """
    from .. import jit as jit_mod
    from ..ops.special import top_p_sampling

    if kv_cache not in ("dense", "paged"):
        raise ValueError(f"kv_cache must be 'dense' or 'paged', "
                         f"got {kv_cache!r}")
    decode, prefill_fn, hard_limit = _decode_fn(model)
    ids = np.asarray(input_ids.numpy()
                     if isinstance(input_ids, Tensor) else input_ids)
    batch, prompt_len = ids.shape
    max_len = prompt_len + max_new_tokens
    cfg = model.cfg
    if max_len > cfg.max_seq_len:
        if hard_limit:  # learned position table: out-of-range = garbage
            raise ValueError(f"max_len {max_len} exceeds max_seq_len "
                             f"{cfg.max_seq_len}")
        import warnings
        warnings.warn(f"generating past max_seq_len ({max_len} > "
                      f"{cfg.max_seq_len}): rope extrapolation territory")
    if kv_cache == "paged":
        import functools
        caches, bt = _empty_paged_caches(model, batch, max_len, page_size)
        attend = functools.partial(paged_cache_attention,
                                   block_tables=bt.tolist())
        write = functools.partial(paged_cache_prefill,
                                  block_tables=bt.tolist())
    else:
        caches = _empty_caches(model, batch, max_len)
        attend = cache_attention
        write = cache_prefill
    if decode_window is None:
        decode_window = 8 if (top_p is None and temperature == 1.0) else 1
    was_training = model.training
    model.eval()
    try:
        return _generate_loop(model, decode, prefill_fn, ids, batch,
                              prompt_len, max_len, max_new_tokens,
                              temperature, top_p, eos_token_id, seed,
                              use_jit, caches, attend, write, kv_cache,
                              prefill, decode_window)
    finally:
        if was_training:
            model.train()


def _generate_loop(model, decode, prefill_fn, ids, batch, prompt_len,
                   max_len, max_new_tokens, temperature, top_p,
                   eos_token_id, seed, use_jit, caches,
                   attend=cache_attention, write=cache_prefill,
                   kv_cache="dense", prefill=True, decode_window=1):
    from .. import jit as jit_mod
    from ..ops.special import top_p_sampling

    # compiled decode step cached per (batch, max_len) ON the model:
    # repeat generate() calls reuse the program instead of re-tracing.
    # page geometry is part of the key: the attend closure bakes in the
    # block table, whose shape depends on page_size.
    n_pages = caches[0].shape[1] if kv_cache == "paged" else 0
    cache_key = (batch, max_len, kv_cache, n_pages)
    step_cache = model.__dict__.setdefault("_decode_step_cache", {})
    step_fn = step_cache.get(cache_key)
    if step_fn is None:

        def step(tok, pos, *cs):
            import paddle_tpu as pp
            with pp.no_grad():
                logits, new = decode(model, tok, pos, list(cs),
                                     attend=attend)
            return (logits,) + tuple(new)

        step_fn = jit_mod.to_static(step) if use_jit else step
        if use_jit:
            step_cache[cache_key] = step_fn

    out = np.concatenate(
        [ids, np.zeros((batch, max_new_tokens), ids.dtype)], axis=1)
    finished = np.zeros(batch, bool)

    # batched prefill: ONE compiled whole-prompt pass fills the caches
    # and yields the first sampled token, replacing prompt_len-1 decode
    # steps (cached per (batch, prompt_len, cache kind) on the model)
    t_start = 0
    prefill_logits = None
    if prefill and prompt_len > 1:
        pf_key = ("prefill", batch, prompt_len, kv_cache, n_pages)
        pf_fn = step_cache.get(pf_key)
        if pf_fn is None:

            def pf(tok_ids, *cs):
                import paddle_tpu as pp
                with pp.no_grad():
                    logits, new = prefill_fn(model, tok_ids, list(cs),
                                             write)
                return (logits,) + tuple(new)

            pf_fn = jit_mod.to_static(pf) if use_jit else pf
            if use_jit:
                step_cache[pf_key] = pf_fn
        res = pf_fn(Tensor(jnp.asarray(ids.astype(np.int32))), *caches)
        prefill_logits, caches = res[0], list(res[1:])
        t_start = prompt_len - 1

    t = t_start
    while t < max_len - 1:  # last token needs no forward
        # windowed fast path: K tokens per dispatch, sampling on device.
        # Needs a compiled step (>=1 scalar call done), generation-region
        # positions, and >=2 tokens left in the window.
        if (decode_window > 1 and use_jit and t >= prompt_len - 1
                and t > t_start):
            wrapped = (step_fn if hasattr(step_fn, "_cache")
                       else getattr(step_fn, "__wrapped__", None))
            exe = (next(iter(wrapped._cache.values()), None)
                   if wrapped is not None and wrapped._cache else None)
            remaining = max_len - 1 - t
            if exe is not None and remaining >= 2:
                t = _run_decode_windows(
                    exe, out, t, remaining, decode_window,
                    caches, finished, temperature, top_p, eos_token_id,
                    seed)
                if eos_token_id is not None and finished.all():
                    # trim exactly where the scalar path would: one past
                    # the LAST row's first eos (windows may have written
                    # eos padding beyond it)
                    hit = out[:, prompt_len:t + 1] == eos_token_id
                    cols = prompt_len + hit.argmax(1)
                    out = out[:, :int(cols.max()) + 1]
                break

        if t == t_start and prefill_logits is not None:
            logits = prefill_logits
        else:
            tok = Tensor(jnp.asarray(out[:, t:t + 1].astype(np.int32)))
            pos = Tensor(jnp.asarray([t], jnp.int32))
            res = step_fn(tok, pos, *caches)
            logits, caches = res[0], list(res[1:])
        if t < prompt_len - 1:
            t += 1
            continue  # prompt region: ignore logits, just fill the cache
        lg = logits.numpy().astype(np.float32)
        if temperature != 1.0:
            lg = lg / max(temperature, 1e-6)
        if top_p is not None:
            # per-step key: seed+t keeps a seeded STREAM, not one quantile
            _, nxt = top_p_sampling(
                Tensor(jnp.asarray(lg)),
                Tensor(jnp.full((batch,), float(top_p))),
                seed=None if seed is None else seed + t)
            nxt = nxt.numpy().reshape(-1)
        elif temperature != 1.0:
            # temperature-only: categorical over the softened logits
            # (argmax would be scale-invariant, i.e. silently greedy)
            rng_t = np.random.default_rng(
                None if seed is None else seed + t)
            p = np.exp(lg - lg.max(-1, keepdims=True))
            p = p / p.sum(-1, keepdims=True)
            nxt = np.array([rng_t.choice(p.shape[-1], p=row)
                            for row in p])
        else:
            nxt = lg.argmax(-1)
        if eos_token_id is not None:
            nxt = np.where(finished, eos_token_id, nxt)
            finished |= (nxt == eos_token_id)
        out[:, t + 1] = nxt.astype(out.dtype)
        if eos_token_id is not None and finished.all():
            out = out[:, :t + 2]
            break
        t += 1
    return Tensor(jnp.asarray(out.astype(np.int32)))


def _run_decode_windows(exe, out, t, remaining, decode_window,
                        caches, finished, temperature, top_p,
                        eos_token_id, seed):
    """Drive the scanned decode windows from position ``t`` (whose token
    is already in ``out``) to the end; returns the final position.
    Mutates ``out``/``finished`` in place and writes post-window state
    back onto the captured tensors."""
    has_eos = eos_token_id is not None
    capt = exe.capt_state
    carry_idx, const_idx = exe.state_split()
    for sync in exe.discovery.host_syncs:
        sync()
    cache_vals = [c._read() if isinstance(c, Tensor) else jnp.asarray(c)
                  for c in caches]
    cstate = [capt[i]._read() for i in carry_idx]
    const_state = [capt[i]._read() for i in const_idx]
    fin = jnp.asarray(finished)
    eos_id = jnp.int32(eos_token_id if has_eos else 0)
    # seed=None must stay genuinely random per call (the scalar path
    # draws fresh host randomness) — pull entropy from numpy
    key = jax.random.PRNGKey(
        seed if seed is not None
        else int(np.random.default_rng().integers(2 ** 31)))
    tok = jnp.asarray(out[:, t:t + 1].astype(np.int32))
    pos = jnp.asarray([t], jnp.int32)

    runners = exe.__dict__.setdefault("_decode_window_cache", {})
    # always run FULL windows (one compiled program per sampling config,
    # never per tail length); overshoot steps write into cache slots that
    # are discarded with the caches, and their tokens are sliced off
    K = decode_window
    rkey = (K, temperature, top_p, has_eos)
    runner = runners.get(rkey)
    if runner is None:
        runner = _make_decode_window(exe, K, temperature, top_p, has_eos)
        runners[rkey] = runner
        # whole-program audit once per window program (compile time
        # only; tracing does not consume the donated cache buffers)
        from .. import analysis as _analysis
        _analysis.audit_jitted(
            runner,
            (tok, pos, cache_vals, cstate, const_state, fin, eos_id,
             key),
            where=f"decode_window.{getattr(exe, '_fn_name', 'step')}")
    while remaining > 0:
        toks, tok, pos, cache_vals, cstate, fin, key = runner(
            tok, pos, cache_vals, cstate, const_state, fin, eos_id, key)
        valid = min(K, remaining)
        toks_np = np.asarray(toks)[:valid]       # [valid, B]
        out[:, t + 1:t + 1 + valid] = toks_np.T.astype(out.dtype)
        t += valid
        remaining -= valid
        if has_eos:
            # host mask from the WRITTEN tokens only (the device mask may
            # include overshoot-step hits on the final window)
            finished[:] = finished | (toks_np == eos_token_id).any(0)
            if finished.all():
                break
    for i, v in zip(carry_idx, cstate):
        capt[i]._data = v
        capt[i]._node = None
    return t


# ===================================================================
# Tensor-parallel serving programs (ISSUE 13; ``inference/distserve``)
# ===================================================================
#
# The serving engine's two compiled programs re-built for a mesh axis:
# weights column/row-split per the canonical Megatron rules
# (``GPTForCausalLMPipe.TP_RULES`` / ``shard_gpt``, re-laid-out
# HEAD-MAJOR so a ``PartitionSpec`` can split the fused qkv projection
# along heads instead of along its interleaved flat output dim), KV
# page pools sharded by kv-head, block tables / lengths / packing
# vectors replicated.  The program body runs under a fully-MANUAL
# ``core.meshutil.shard_map`` (the Pallas ragged kernel cannot be
# GSPMD-partitioned) with
# exactly ONE ``psum`` at the attention output projection and one at
# the MLP down-projection per layer — the textbook Megatron cut.
#
# GQA awareness: when ``Hk % tp == 0`` the K/V projections and pools
# shard with the q heads (contiguous head blocks keep the q->kv GQA
# mapping local).  When ``Hk < tp`` (and ``tp % Hk == 0``) the K/V
# side REPLICATES: every shard computes and writes all kv heads
# (identical bytes — the write is per-head deterministic), and each
# shard attends its q heads against a 1-head dynamic slice of the
# replicated pools (``tp/Hk`` consecutive shards serve one kv head).
#
# Greedy outputs are token-identical to the single-device engine: the
# only numerical difference is the psum's split reduction order
# (last-ulp on the logits), which the serving parity suite pins at the
# token level.

def _tp_axis_size(jmesh, axis):
    sizes = dict(zip(jmesh.axis_names, jmesh.devices.shape))
    if axis not in sizes:
        raise ValueError(
            f"tp_axis {axis!r} is not a mesh axis {jmesh.axis_names}")
    return int(sizes[axis])


class TPParams:
    """A model's weights re-laid-out + device_put for manual TP.

    ``names``/``vals``/``specs`` are parallel lists (the shard_map
    inputs and their ``PartitionSpec``s); ``meta`` carries the local
    geometry the program bodies need.  Extraction is a read-only
    SNAPSHOT of the model (serving engines own eval-mode models; the
    single-device engine sharing the model instance is untouched)."""

    __slots__ = ("names", "vals", "specs", "meta")

    def __init__(self, names, vals, specs, meta):
        self.names = names
        self.vals = vals
        self.specs = specs
        self.meta = meta


def tp_shard_params(model, jmesh, tp_axis):
    """Extract + shard a GPT/LLaMA's weights for the TP serving
    programs.  See the section comment for the layout; raises on head
    counts the cut cannot serve (``Hq % tp``, and for GQA
    ``Hk % tp and tp % Hk``)."""
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .gpt import GPTForCausalLM
    from .llama import LlamaForCausalLM

    tp = _tp_axis_size(jmesh, tp_axis)
    cfg = model.cfg
    nh = cfg.num_heads
    hd = cfg.head_dim
    nhk = getattr(cfg, "num_kv_heads", nh)
    if nh % tp:
        raise ValueError(
            f"serving TP: num_heads {nh} not divisible by tp={tp}")
    if cfg.intermediate_size % tp:
        raise ValueError(
            f"serving TP: intermediate_size {cfg.intermediate_size} "
            f"not divisible by tp={tp}")
    shard_kv = nhk % tp == 0
    if not shard_kv and tp % nhk:
        raise ValueError(
            f"serving TP: GQA kv heads {nhk} neither divisible by nor "
            f"a divisor of tp={tp}")
    names, vals, specs = [], [], []

    def add(name, val, spec):
        names.append(name)
        vals.append(_jax.device_put(val, NamedSharding(jmesh, spec)))
        specs.append(spec)

    col = P(None, tp_axis)          # [h, out] split on out
    row = P(tp_axis)                # leading dim split
    rep = P()
    if isinstance(model, GPTForCausalLM):
        gpt = model.gpt
        add("wte", gpt.wte.weight._read(), rep)
        add("wpe", gpt.wpe.weight._read(), rep)
        for li, blk in enumerate(gpt.blocks):
            h = cfg.hidden_size
            add(f"b{li}.ln1.w", blk.ln1.weight._read(), rep)
            add(f"b{li}.ln1.b", blk.ln1.bias._read(), rep)
            # fused qkv: flat out dim is (3, nh, hd)-interleaved — a
            # contiguous column split would cut across q/k/v, so the
            # weight reshapes head-major and shards the HEAD dim
            add(f"b{li}.qkv.w",
                blk.attn.qkv.weight._read().reshape(h, 3, nh, hd),
                P(None, None, tp_axis))
            add(f"b{li}.qkv.b",
                blk.attn.qkv.bias._read().reshape(3, nh, hd),
                P(None, tp_axis))
            add(f"b{li}.proj.w",
                blk.attn.proj.weight._read().reshape(nh, hd, h), row)
            add(f"b{li}.proj.b", blk.attn.proj.bias._read(), rep)
            add(f"b{li}.ln2.w", blk.ln2.weight._read(), rep)
            add(f"b{li}.ln2.b", blk.ln2.bias._read(), rep)
            add(f"b{li}.fc1.w", blk.mlp.fc1.weight._read(), col)
            add(f"b{li}.fc1.b", blk.mlp.fc1.bias._read(), row)
            add(f"b{li}.fc2.w", blk.mlp.fc2.weight._read(), row)
            add(f"b{li}.fc2.b", blk.mlp.fc2.bias._read(), rep)
        add("ln_f.w", gpt.ln_f.weight._read(), rep)
        add("ln_f.b", gpt.ln_f.bias._read(), rep)
        if model.lm_head is not None:
            add("lm_head", model.lm_head.weight._read(), rep)
        family = "gpt"
    elif isinstance(model, LlamaForCausalLM):
        lm = model.llama
        add("wte", lm.embed_tokens.weight._read(), rep)
        for li, layer in enumerate(lm.layers):
            a = layer.attn
            h = cfg.hidden_size
            add(f"b{li}.in_norm.w", layer.input_norm.weight._read(),
                rep)
            add(f"b{li}.q.w",
                a.q_proj.weight._read().reshape(h, nh, hd),
                P(None, tp_axis))
            add(f"b{li}.k.w",
                a.k_proj.weight._read().reshape(h, nhk, hd),
                P(None, tp_axis) if shard_kv else rep)
            add(f"b{li}.v.w",
                a.v_proj.weight._read().reshape(h, nhk, hd),
                P(None, tp_axis) if shard_kv else rep)
            add(f"b{li}.o.w",
                a.o_proj.weight._read().reshape(nh, hd, h), row)
            add(f"b{li}.post_norm.w", layer.post_norm.weight._read(),
                rep)
            add(f"b{li}.gate.w", layer.mlp.gate_proj.weight._read(),
                col)
            add(f"b{li}.up.w", layer.mlp.up_proj.weight._read(), col)
            add(f"b{li}.down.w", layer.mlp.down_proj.weight._read(),
                row)
        add("norm.w", lm.norm.weight._read(), rep)
        if model.lm_head is not None:
            add("lm_head", model.lm_head.weight._read(), rep)
        family = "llama"
    else:
        raise TypeError(
            f"serving TP: unsupported model {type(model).__name__}")
    meta = {
        "family": family, "tp": tp, "axis": tp_axis,
        "nh_loc": nh // tp,
        "nhk_loc": nhk // tp if shard_kv else nhk,
        "shard_kv": shard_kv, "hd": hd,
        "shards_per_kv": 1 if shard_kv else tp // nhk,
    }
    return TPParams(names, vals, specs, meta)


def tp_cache_spec(meta, tp_axis):
    """PartitionSpec of one KV page pool (or scale side-pool) under
    this TP layout: sharded on the kv-head dim when ``Hk % tp == 0``,
    replicated otherwise (every shard writes all heads — identical
    bytes by construction)."""
    from jax.sharding import PartitionSpec as P
    return P(tp_axis) if meta["shard_kv"] else P()


def _tp_kv_slice(meta, pools, tp_axis):
    """The kv-head slice of (replicated) ``pools`` this shard attends
    with, or ``pools`` unchanged when they are head-sharded.  With
    ``Hk < tp``, ``tp/Hk`` consecutive shards serve one kv head, so
    the slice is ONE head at a traced per-shard offset."""
    if meta["shard_kv"]:
        return pools
    from jax import lax as _lax
    r = _lax.axis_index(meta["axis"])
    head = r // meta["shards_per_kv"]
    return [_lax.dynamic_slice_in_dim(p, head, 1, axis=0)
            for p in pools]


def _tp_attend_ragged(meta, q, kn, vn, kp, vp, tok_pos, tok_slot,
                      tok_valid, kv_lens, q_lens, bt, q_block, ppb,
                      ks=None, vs=None):
    """One layer's packed-token page write + ragged attention under
    TP.  Head-sharded pools go straight through
    :func:`ragged_paged_step`'s jnp body; replicated pools (GQA
    ``Hk < tp``) write ALL heads through the SAME
    :func:`_ragged_page_write` home (bytes cannot drift between the
    modes) and attend a 1-head slice."""
    from ..ops.pallas.paged_attention import ragged_paged_attention

    if meta["shard_kv"]:
        outs = ragged_paged_step.raw(
            q, kn, vn, kp, vp, tok_pos, tok_slot, tok_valid, kv_lens,
            q_lens, bt, q_block=q_block, pages_per_block=ppb,
            k_scales=ks, v_scales=vs)
        if ks is not None:
            att, kp, vp, ks, vs = outs
            return att, kp, vp, ks, vs
        att, kp, vp = outs
        return att, kp, vp, None, None
    bt_i = bt.astype(jnp.int32)
    knn = jnp.swapaxes(kn, 0, 1)                   # [Hk, T, D] (full)
    vnn = jnp.swapaxes(vn, 0, 1)
    kp, vp, ks, vs = _ragged_page_write(
        knn, vnn, kp, vp, bt_i, tok_pos, tok_slot, tok_valid, ks, vs)
    kp_s, vp_s = _tp_kv_slice(meta, [kp, vp], meta["axis"])
    sc_s = (_tp_kv_slice(meta, [ks, vs], meta["axis"])
            if ks is not None else (None, None))
    att = ragged_paged_attention(
        q, kp_s, vp_s, bt_i, kv_lens.astype(jnp.int32),
        q_lens.astype(jnp.int32), q_block=q_block,
        pages_per_block=ppb, k_scales=sc_s[0], v_scales=sc_s[1])
    return att.astype(q.dtype), kp, vp, ks, vs


def _tp_attend_decode(meta, q, kn, vn, kp, vp, positions, bt, ppb,
                      ks=None, vs=None):
    """Per-slot decode-step analog of :func:`_tp_attend_ragged`
    (replicated-KV writes go through :func:`_slot_page_write`, the
    same home ``paged_slot_attention`` uses)."""
    from ..ops.pallas.paged_attention import paged_decode_attention

    if meta["shard_kv"]:
        outs = paged_slot_attention.raw(
            q, kn, vn, kp, vp, positions, bt, pages_per_block=ppb,
            k_scales=ks, v_scales=vs)
        if ks is not None:
            att, kp, vp, ks, vs = outs
            return att, kp, vp, ks, vs
        att, kp, vp = outs
        return att, kp, vp, None, None
    p = positions.reshape(-1).astype(jnp.int32)
    bt_i = bt.astype(jnp.int32)
    knn = jnp.swapaxes(kn[:, 0], 0, 1)             # [Hk, B, D] (full)
    vnn = jnp.swapaxes(vn[:, 0], 0, 1)
    kp, vp, ks, vs = _slot_page_write(knn, vnn, kp, vp, bt_i,
                                      positions, ks, vs)
    kp_s, vp_s = _tp_kv_slice(meta, [kp, vp], meta["axis"])
    sc_s = (_tp_kv_slice(meta, [ks, vs], meta["axis"])
            if ks is not None else (None, None))
    att = paged_decode_attention(
        q[:, 0], kp_s, vp_s, bt_i, p + 1, pages_per_block=ppb,
        k_scales=sc_s[0], v_scales=sc_s[1])
    return att[:, None].astype(q.dtype), kp, vp, ks, vs


def _gpt_tp_body(model, tpp, q_block, ppb):
    """(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens, bt, *flat)
    -> (logits [T, V] tp-replicated, new caches local) — the packed
    ragged forward under manual TP (shard_map body)."""
    from jax import lax as _lax

    from ..distributed.fleet.pipeline import functional_call
    from ..nn.functional.activation import _gelu_impl

    gpt = model.gpt
    meta = tpp.meta
    names = tpp.names
    n_p = len(names)
    L = len(gpt.blocks)
    axis = meta["axis"]

    def body(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens, bt,
             *flat):
        pv = dict(zip(names, flat[:n_p]))
        caches = list(flat[n_p:])
        data, scales = _split_caches(caches, L)
        t = ids.shape[1]
        x = functional_call(gpt.wte, {"weight": pv["wte"]}, ids) \
            + functional_call(gpt.wpe, {"weight": pv["wpe"]},
                              tok_pos.reshape(1, -1))
        new, new_sc = [], []
        for li, blk in enumerate(gpt.blocks):
            h = functional_call(
                blk.ln1, {"weight": pv[f"b{li}.ln1.w"],
                          "bias": pv[f"b{li}.ln1.b"]}, x)
            h2 = h.reshape(t, -1)
            wq = pv[f"b{li}.qkv.w"]          # [h, 3, nh_loc, hd]
            qkv = (h2 @ wq.reshape(wq.shape[0], -1)
                   + pv[f"b{li}.qkv.b"].reshape(-1))
            qkv = qkv.reshape(t, 3, wq.shape[2], wq.shape[3])
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
            ks = scales[2 * li] if scales else None
            vs = scales[2 * li + 1] if scales else None
            att, kc, vc, ks, vs = _tp_attend_ragged(
                meta, q, k, v, data[2 * li], data[2 * li + 1],
                tok_pos, tok_slot, tok_valid, kv_lens, q_lens, bt,
                q_block, ppb, ks, vs)
            new.extend([kc, vc])
            if ks is not None:
                new_sc.extend([ks, vs])
            wp = pv[f"b{li}.proj.w"]         # [nh_loc, hd, h]
            prj = att.reshape(t, -1) @ wp.reshape(-1, wp.shape[-1])
            prj = _lax.psum(prj, axis) + pv[f"b{li}.proj.b"]
            x = x + prj.reshape(1, t, -1)
            h = functional_call(
                blk.ln2, {"weight": pv[f"b{li}.ln2.w"],
                          "bias": pv[f"b{li}.ln2.b"]}, x)
            f1 = h.reshape(t, -1) @ pv[f"b{li}.fc1.w"] \
                + pv[f"b{li}.fc1.b"]
            f1 = _gelu_impl.raw(f1, approximate=True)
            f2 = f1 @ pv[f"b{li}.fc2.w"]
            f2 = _lax.psum(f2, axis) + pv[f"b{li}.fc2.b"]
            x = x + f2.reshape(1, t, -1)
        hf = functional_call(
            gpt.ln_f, {"weight": pv["ln_f.w"], "bias": pv["ln_f.b"]},
            x).reshape(t, -1)
        if model.lm_head is not None:
            logits = hf @ pv["lm_head"]
        else:
            logits = hf @ pv["wte"].T
        return logits, new + new_sc

    return body


def _gpt_tp_decode_body(model, tpp, ppb):
    """(tok [B,1], pos [B], bt, *flat) -> (logits [B, V], new caches)
    — the per-slot decode step under manual TP."""
    from jax import lax as _lax

    from ..distributed.fleet.pipeline import functional_call
    from ..nn.functional.activation import _gelu_impl

    gpt = model.gpt
    meta = tpp.meta
    names = tpp.names
    n_p = len(names)
    L = len(gpt.blocks)
    axis = meta["axis"]

    def body(tok, pos, bt, *flat):
        pv = dict(zip(names, flat[:n_p]))
        caches = list(flat[n_p:])
        data, scales = _split_caches(caches, L)
        b = tok.shape[0]
        x = functional_call(gpt.wte, {"weight": pv["wte"]}, tok) \
            + functional_call(gpt.wpe, {"weight": pv["wpe"]},
                              pos.reshape(-1, 1))
        new, new_sc = [], []
        for li, blk in enumerate(gpt.blocks):
            h = functional_call(
                blk.ln1, {"weight": pv[f"b{li}.ln1.w"],
                          "bias": pv[f"b{li}.ln1.b"]}, x)
            h2 = h.reshape(b, -1)
            wq = pv[f"b{li}.qkv.w"]
            qkv = (h2 @ wq.reshape(wq.shape[0], -1)
                   + pv[f"b{li}.qkv.b"].reshape(-1))
            qkv = qkv.reshape(b, 1, 3, wq.shape[2], wq.shape[3])
            q, k, v = (qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
            ks = scales[2 * li] if scales else None
            vs = scales[2 * li + 1] if scales else None
            att, kc, vc, ks, vs = _tp_attend_decode(
                meta, q, k, v, data[2 * li], data[2 * li + 1], pos,
                bt, ppb, ks, vs)
            new.extend([kc, vc])
            if ks is not None:
                new_sc.extend([ks, vs])
            wp = pv[f"b{li}.proj.w"]
            prj = att.reshape(b, -1) @ wp.reshape(-1, wp.shape[-1])
            prj = _lax.psum(prj, axis) + pv[f"b{li}.proj.b"]
            x = x + prj.reshape(b, 1, -1)
            h = functional_call(
                blk.ln2, {"weight": pv[f"b{li}.ln2.w"],
                          "bias": pv[f"b{li}.ln2.b"]}, x)
            f1 = h.reshape(b, -1) @ pv[f"b{li}.fc1.w"] \
                + pv[f"b{li}.fc1.b"]
            f1 = _gelu_impl.raw(f1, approximate=True)
            f2 = f1 @ pv[f"b{li}.fc2.w"]
            f2 = _lax.psum(f2, axis) + pv[f"b{li}.fc2.b"]
            x = x + f2.reshape(b, 1, -1)
        hf = functional_call(
            gpt.ln_f, {"weight": pv["ln_f.w"], "bias": pv["ln_f.b"]},
            x).reshape(b, -1)
        if model.lm_head is not None:
            logits = hf @ pv["lm_head"]
        else:
            logits = hf @ pv["wte"].T
        return logits, new + new_sc

    return body


def _llama_tp_body(model, tpp, q_block, ppb):
    from jax import lax as _lax

    from ..distributed.fleet.pipeline import functional_call

    lm = model.llama
    meta = tpp.meta
    names = tpp.names
    n_p = len(names)
    L = len(lm.layers)
    axis = meta["axis"]

    def body(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens, bt,
             *flat):
        import jax as _jax
        pv = dict(zip(names, flat[:n_p]))
        caches = list(flat[n_p:])
        data, scales = _split_caches(caches, L)
        t = ids.shape[1]
        x = functional_call(lm.embed_tokens, {"weight": pv["wte"]},
                            ids)
        new, new_sc = [], []
        for li, layer in enumerate(lm.layers):
            a = layer.attn
            h = functional_call(
                layer.input_norm,
                {"weight": pv[f"b{li}.in_norm.w"]}, x)
            h2 = h.reshape(t, -1)
            wqq = pv[f"b{li}.q.w"]           # [h, nh_loc, hd]
            wkk = pv[f"b{li}.k.w"]           # [h, nhk_loc|nhk, hd]
            wvv = pv[f"b{li}.v.w"]
            q = (h2 @ wqq.reshape(wqq.shape[0], -1)).reshape(
                1, t, wqq.shape[1], wqq.shape[2])
            k = (h2 @ wkk.reshape(wkk.shape[0], -1)).reshape(
                1, t, wkk.shape[1], wkk.shape[2])
            v = (h2 @ wvv.reshape(wvv.shape[0], -1)).reshape(
                1, t, wvv.shape[1], wvv.shape[2])
            q = rope_at.raw(q, tok_pos, theta=a.rope_theta)
            k = rope_at.raw(k, tok_pos, theta=a.rope_theta)
            ks = scales[2 * li] if scales else None
            vs = scales[2 * li + 1] if scales else None
            att, kc, vc, ks, vs = _tp_attend_ragged(
                meta, q.reshape(t, wqq.shape[1], wqq.shape[2]),
                k.reshape(t, wkk.shape[1], wkk.shape[2]),
                v.reshape(t, wvv.shape[1], wvv.shape[2]),
                data[2 * li], data[2 * li + 1], tok_pos, tok_slot,
                tok_valid, kv_lens, q_lens, bt, q_block, ppb, ks, vs)
            new.extend([kc, vc])
            if ks is not None:
                new_sc.extend([ks, vs])
            wo = pv[f"b{li}.o.w"]            # [nh_loc, hd, h]
            prj = att.reshape(t, -1) @ wo.reshape(-1, wo.shape[-1])
            prj = _lax.psum(prj, axis)
            x = x + prj.reshape(1, t, -1)
            h = functional_call(
                layer.post_norm,
                {"weight": pv[f"b{li}.post_norm.w"]}, x)
            h2 = h.reshape(t, -1)
            f1 = _jax.nn.silu(h2 @ pv[f"b{li}.gate.w"]) \
                * (h2 @ pv[f"b{li}.up.w"])
            f2 = f1 @ pv[f"b{li}.down.w"]
            f2 = _lax.psum(f2, axis)
            x = x + f2.reshape(1, t, -1)
        hf = functional_call(lm.norm, {"weight": pv["norm.w"]},
                             x).reshape(t, -1)
        if model.lm_head is not None:
            logits = hf @ pv["lm_head"]
        else:
            logits = hf @ pv["wte"].T
        return logits, new + new_sc

    return body


def _llama_tp_decode_body(model, tpp, ppb):
    from jax import lax as _lax

    from ..distributed.fleet.pipeline import functional_call

    lm = model.llama
    meta = tpp.meta
    names = tpp.names
    n_p = len(names)
    L = len(lm.layers)
    axis = meta["axis"]

    def body(tok, pos, bt, *flat):
        import jax as _jax
        pv = dict(zip(names, flat[:n_p]))
        caches = list(flat[n_p:])
        data, scales = _split_caches(caches, L)
        b = tok.shape[0]
        x = functional_call(lm.embed_tokens, {"weight": pv["wte"]},
                            tok)
        new, new_sc = [], []
        for li, layer in enumerate(lm.layers):
            a = layer.attn
            h = functional_call(
                layer.input_norm,
                {"weight": pv[f"b{li}.in_norm.w"]}, x)
            h2 = h.reshape(b, -1)
            wqq = pv[f"b{li}.q.w"]
            wkk = pv[f"b{li}.k.w"]
            wvv = pv[f"b{li}.v.w"]
            q = (h2 @ wqq.reshape(wqq.shape[0], -1)).reshape(
                b, 1, wqq.shape[1], wqq.shape[2])
            k = (h2 @ wkk.reshape(wkk.shape[0], -1)).reshape(
                b, 1, wkk.shape[1], wkk.shape[2])
            v = (h2 @ wvv.reshape(wvv.shape[0], -1)).reshape(
                b, 1, wvv.shape[1], wvv.shape[2])
            q = rope_at.raw(q, pos, theta=a.rope_theta)
            k = rope_at.raw(k, pos, theta=a.rope_theta)
            ks = scales[2 * li] if scales else None
            vs = scales[2 * li + 1] if scales else None
            att, kc, vc, ks, vs = _tp_attend_decode(
                meta, q, k, v, data[2 * li], data[2 * li + 1], pos,
                bt, ppb, ks, vs)
            new.extend([kc, vc])
            if ks is not None:
                new_sc.extend([ks, vs])
            wo = pv[f"b{li}.o.w"]
            prj = att.reshape(b, -1) @ wo.reshape(-1, wo.shape[-1])
            prj = _lax.psum(prj, axis)
            x = x + prj.reshape(b, 1, -1)
            h = functional_call(
                layer.post_norm,
                {"weight": pv[f"b{li}.post_norm.w"]}, x)
            h2 = h.reshape(b, -1)
            f1 = _jax.nn.silu(h2 @ pv[f"b{li}.gate.w"]) \
                * (h2 @ pv[f"b{li}.up.w"])
            f2 = f1 @ pv[f"b{li}.down.w"]
            f2 = _lax.psum(f2, axis)
            x = x + f2.reshape(b, 1, -1)
        hf = functional_call(lm.norm, {"weight": pv["norm.w"]},
                             x).reshape(b, -1)
        if model.lm_head is not None:
            logits = hf @ pv["lm_head"]
        else:
            logits = hf @ pv["wte"].T
        return logits, new + new_sc

    return body


def _tp_body_fns(model):
    from .gpt import GPTForCausalLM
    from .llama import LlamaForCausalLM
    if isinstance(model, GPTForCausalLM):
        return _gpt_tp_body, _gpt_tp_decode_body
    if isinstance(model, LlamaForCausalLM):
        return _llama_tp_body, _llama_tp_decode_body
    raise TypeError(
        f"serving TP: unsupported model {type(model).__name__}")


def make_tp_mixed(model, tpp, jmesh, q_block, ppb, n_caches):
    """The TP MIXED serving program: same call signature as the
    single-device engine's compiled mixed step (packing vectors +
    poison + block tables + cache pools), jitted over a fully-manual
    shard_map of the TP forward, ``guarded_argmax`` running replicated
    after the final psum so every shard returns the identical token
    and bad-flag vectors."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from ..core.meshutil import shard_map
    meta = tpp.meta
    axis = meta["axis"]
    ragged_body, _ = _tp_body_fns(model)
    body = ragged_body(model, tpp, q_block, ppb)
    cspec = tp_cache_spec(meta, axis)

    def mixed(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens,
              last_idx, poison, bt, *flat):
        logits, new = body(ids, tok_pos, tok_slot, tok_valid, kv_lens,
                           q_lens, bt, *flat)
        lg = logits[last_idx]                         # [B, V]
        nxt, bad = guarded_argmax.raw(lg, poison)
        return (nxt, bad) + tuple(new)

    rep = P()
    in_specs = (rep,) * 9 + tuple(tpp.specs) \
        + (cspec,) * n_caches
    out_specs = (rep, rep) + (cspec,) * n_caches
    return _jax.jit(shard_map(mixed, jmesh, in_specs=in_specs,
                              out_specs=out_specs))


def make_tp_spec(model, tpp, jmesh, q_block, ppb, n_caches,
                 need_logits):
    """The TP speculative VERIFY program (``verify_argmax`` over the
    packed logits; ``need_logits`` adds the gathered per-slot logits
    rows the sampling acceptance rule consumes)."""
    import jax as _jax
    from jax.sharding import PartitionSpec as P

    from ..core.meshutil import shard_map
    meta = tpp.meta
    axis = meta["axis"]
    ragged_body, _ = _tp_body_fns(model)
    body = ragged_body(model, tpp, q_block, ppb)
    cspec = tp_cache_spec(meta, axis)
    rep = P()

    if need_logits:
        def spec(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens,
                 poison, gather_idx, bt, *flat):
            logits, new = body(ids, tok_pos, tok_slot, tok_valid,
                               kv_lens, q_lens, bt, *flat)
            toks, bad = verify_argmax.raw(logits, tok_slot, tok_valid,
                                          poison)
            return (toks, bad, logits[gather_idx]) + tuple(new)
        n_in, n_head = 9, 3
    else:
        def spec(ids, tok_pos, tok_slot, tok_valid, kv_lens, q_lens,
                 poison, bt, *flat):
            logits, new = body(ids, tok_pos, tok_slot, tok_valid,
                               kv_lens, q_lens, bt, *flat)
            toks, bad = verify_argmax.raw(logits, tok_slot, tok_valid,
                                          poison)
            return (toks, bad) + tuple(new)
        n_in, n_head = 8, 2

    in_specs = (rep,) * n_in + tuple(tpp.specs) + (cspec,) * n_caches
    out_specs = (rep,) * n_head + (cspec,) * n_caches
    return _jax.jit(shard_map(spec, jmesh, in_specs=in_specs,
                              out_specs=out_specs))


def make_tp_window(model, tpp, jmesh, ppb, n_caches, K):
    """K scanned TP decode steps in ONE dispatch — the
    ``_make_slot_window`` analog with explicit params instead of
    captured executable state.  Same carry (token, position, finished,
    guard-bad per slot + caches), same freeze rule, same stacked
    per-step bad flags; cache pools are donated."""
    import jax as _jax
    from jax import lax as _lax
    from jax.sharding import PartitionSpec as P

    from ..core.meshutil import shard_map
    meta = tpp.meta
    axis = meta["axis"]
    _, decode_body_fn = _tp_body_fns(model)
    step_body = decode_body_fn(model, tpp, ppb)
    cspec = tp_cache_spec(meta, axis)
    rep = P()
    n_p = len(tpp.names)

    def window(tok, pos, fin, bad, eos_ids, stop_lens, poison, bt,
               *flat):
        params = flat[:n_p]
        caches = list(flat[n_p:])

        def body(c, _):
            tok, pos, fin, bad, caches = c
            lg, new_caches = step_body(tok, pos, bt, *params, *caches)
            lg = lg.astype(jnp.float32)
            nxt_raw, row_bad = guarded_argmax.raw(lg, poison)
            bad2 = bad | (row_bad & jnp.logical_not(fin))
            adv = jnp.logical_not(fin | bad2)
            nxt = jnp.where(adv, nxt_raw, tok[:, 0])
            pos2 = jnp.where(adv, pos + 1, pos)
            fin2 = fin | bad2 | ((eos_ids >= 0) & (nxt == eos_ids)) \
                | (pos2 + 1 >= stop_lens)
            return (nxt[:, None], pos2, fin2, bad2,
                    list(new_caches)), (nxt, bad2)

        (tok, pos, fin, bad, caches), (toks, bads) = _lax.scan(
            body, (tok, pos, fin, bad, caches), None, length=K)
        return (toks, bads, tok, pos, fin, bad) + tuple(caches)

    in_specs = (rep,) * 8 + tuple(tpp.specs) + (cspec,) * n_caches
    out_specs = (rep,) * 6 + (cspec,) * n_caches
    fn = shard_map(window, jmesh, in_specs=in_specs,
                   out_specs=out_specs)
    # donate the cache pools (the last n_caches positional args)
    donate = tuple(range(8 + n_p, 8 + n_p + n_caches))
    return _jax.jit(fn, donate_argnums=donate)
