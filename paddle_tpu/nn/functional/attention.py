"""Attention functionals.

Analog of ``python/paddle/nn/functional/flash_attention.py`` (reference
``flash_attention.py:147,303,442``; CUDA kernels
``paddle/phi/kernels/gpu/flash_attn_kernel.cu:91``). TPU-native: the public
API keeps paddle's [batch, seq, heads, head_dim] signature; the implementation
dispatches to a Pallas flash-attention kernel on TPU (``paddle_tpu.ops.pallas``)
and falls back to an XLA soft(max(QK))V composition elsewhere (CPU tests).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import apply, primitive


def _use_pallas(q):
    return jax.default_backend() == "tpu"


def _dropout_probs(probs, dropout, key):
    keep = jax.random.bernoulli(key, 1.0 - dropout, probs.shape)
    return jnp.where(keep, probs / (1.0 - dropout),
                     jnp.zeros((), probs.dtype))


def _sdpa_xla(q, k, v, mask=None, dropout=0.0, causal=False, scale=None,
              dropout_key=None, window=None):
    # q,k,v: [B, S, H, D] (paddle layout) -> compute in [B, H, S, D]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    d = qt.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    # grouped-query attention: repeat kv heads if fewer than q heads
    hq, hk = qt.shape[1], kt.shape[1]
    if hk != hq:
        rep = hq // hk
        kt = jnp.repeat(kt, rep, axis=1)
        vt = jnp.repeat(vt, rep, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt,
                        preferred_element_type=jnp.float32) * s
    if causal:
        q_len, k_len = logits.shape[-2], logits.shape[-1]
        idx_q = jnp.arange(q_len)[:, None] + (k_len - q_len)
        idx_k = jnp.arange(k_len)[None, :]
        cmask = idx_q >= idx_k
        if window is not None:
            cmask = cmask & (idx_q - idx_k < window)
        logits = jnp.where(cmask, logits, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -jnp.inf)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(qt.dtype)
    if dropout > 0.0 and dropout_key is not None:
        probs = _dropout_probs(probs, dropout,
                               jax.random.wrap_key_data(
                                   dropout_key.astype(jnp.uint32)))
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None, backend=None,
                                 window=None):
    """paddle.nn.functional.scaled_dot_product_attention parity
    (layout [batch, seq, num_heads, head_dim]).

    ``backend`` (extension over the reference signature): None = auto
    (Pallas flash attention on TPU when eligible), "xla" forces the
    unfused fallback, "pallas" requires the flash kernel.
    ``window`` (extension; with ``is_causal`` only): sliding-window
    attention, query ``i`` sees the ``window`` keys ``i - window < j <=
    i``; the flash kernels and the fallback take it alike."""
    if backend not in (None, "xla", "pallas"):
        raise ValueError(
            f"backend must be None, 'xla' or 'pallas'; got {backend!r}")
    if window is not None and (not is_causal or window < 1):
        raise ValueError(f"window={window!r} needs is_causal=True and at "
                         f"least one key")
    args = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        args.append(attn_mask)
    drop = float(dropout_p) if training else 0.0
    if drop > 0.0:
        from ...core import state
        from ...core.tensor import Tensor
        args.append(Tensor(jax.random.key_data(
            state.default_rng.next_key())))

    def impl(q, k, v, *rest):
        i = 0
        m = rest[i] if has_mask else None
        if has_mask:
            i += 1
        dk = rest[i] if drop > 0.0 else None
        eligible = m is None and drop == 0.0
        if backend == "pallas" and not eligible:
            raise ValueError("backend='pallas' requires no attn_mask and "
                             "dropout_p == 0")
        use_pl = (backend == "pallas" or
                  (backend is None and _use_pallas(q) and eligible))
        if use_pl:
            from ...ops.pallas import flash_attention as fa
            return fa.flash_attention(q, k, v, causal=is_causal,
                                      window=window)
        return _sdpa_xla(q, k, v, mask=m, dropout=drop, causal=is_causal,
                         dropout_key=dk, window=window)

    return apply("scaled_dot_product_attention", impl, *args)


@primitive
def kda_chunk(q, k, v, g, beta, *, chunk=None, scale=None):
    """Gated delta-rule linear attention with a per-channel decay (KDA),
    by chunks: per head ``S_t = (I - b_t k_t k_t^T) diag(exp(g_t))
    S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t * scale`` with q and k
    L2-normalised here and ``scale`` = ``1 / sqrt(dk)`` by default.

    ``q``, ``k``, ``g`` [batch, seq, heads, dk] (``g`` at most 0, the
    decay's logarithm per channel), ``v`` [batch, seq, heads, dv],
    ``beta`` [batch, seq, heads] in (0, 1); the result has v's shape and
    q's dtype.  Forward and a hand-written backward over chunks of
    ``chunk`` positions (``ops/pallas/kda.py``, whose ``CHUNK`` is the
    default): the Pallas kernels on a
    TPU, the same algebra through XLA elsewhere.  Under AMP O2 the
    dispatch hands an op every input in the compute type: a caller
    that wants ``g`` and its running sums in float32 makes the gate
    inside its own op and calls ``kda_chunk.raw``, as
    ``models/kimi_linear.py`` does."""
    from ...ops.pallas import kda
    return kda.kda_chunk(q, k, v, g, beta, chunk=chunk, scale=scale)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle flash_attention parity (reference
    ``nn/functional/flash_attention.py:147``): returns (out, softmax_lse)
    shaped like the reference's (out, None) when return_softmax=False."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_qkvpacked(qkv, dropout=0.0, causal=False,
                         return_softmax=False, training=True, name=None):
    from ... import ops
    q, k, v = ops.unbind(qkv, axis=2)
    return flash_attention(q, k, v, dropout=dropout, causal=causal,
                           return_softmax=return_softmax, training=training)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, training=True,
                        name=None):
    """Varlen flash attention (reference ``flash_attention.py:303``,
    kernel ``flash_attn_kernel.cu:91`` flash_attn_varlen_fwd): packed
    [total_tokens, heads, dim] with cu_seqlens prefix sums.

    On TPU with identically-packed q/k this runs the Pallas flash kernel
    with per-token segment ids (no [S,S] mask ever materializes); otherwise
    it falls back to the masked XLA path (still static-shaped)."""
    args = [query, key, value, cu_seqlens_q, cu_seqlens_k]

    def impl(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        total_k = k.shape[0]
        # segment ids from cu_seqlens: token i belongs to segment
        # sum(cu <= i) - 1
        pos_q = jnp.arange(total_q)
        pos_k = jnp.arange(total_k)
        seg_q = jnp.searchsorted(cu_q, pos_q, side="right") - 1
        seg_k = jnp.searchsorted(cu_k, pos_k, side="right") - 1
        # "same packing" must be decided statically (it picks the traced
        # program): same object always qualifies; equal VALUES qualify only
        # fully eagerly, so a captured program can't diverge between the
        # discovery (concrete) and replay (traced) passes.
        from ...core import tensor as tensor_mod
        same_packing = total_q == total_k and (
            cu_q is cu_k
            or (tensor_mod._tracker is None
                and not isinstance(cu_q, jax.core.Tracer)
                and not isinstance(cu_k, jax.core.Tracer)
                and bool(np.array_equal(np.asarray(cu_q),
                                        np.asarray(cu_k)))))
        if _use_pallas(q) and (same_packing or not causal):
            # per-segment causal == global causal only when q/k share the
            # packing; non-causal needs no position alignment at all
            from ...ops.pallas import flash_attention as fa
            out = fa.flash_attention(
                q[None], k[None], v[None], causal=causal, scale=scale,
                segment_ids=(seg_q[None], seg_k[None]))
            return out[0]
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            off_q = pos_q - jnp.take(cu_q, seg_q)
            off_k = pos_k - jnp.take(cu_k, seg_k)
            mask = mask & (off_q[:, None] >= off_k[None, :])
        out = _sdpa_xla(q[None], k[None], v[None], mask=mask[None, None],
                        scale=scale)
        return out[0]

    out = apply("flash_attn_unpadded", impl, *args)
    return out, None


def sdp_kernel(*a, **k):  # compatibility no-op context
    import contextlib
    return contextlib.nullcontext()
