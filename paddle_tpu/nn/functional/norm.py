"""Normalization functionals.

Analog of ``python/paddle/nn/functional/norm.py`` (reference; fused kernels
``paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu``,
``rms_norm_kernel``). On TPU these are single XLA fusion clusters; stats are
computed in float32 regardless of input dtype (matching the reference's
welford/float accumulate behavior under AMP).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import apply
from ...core.tensor import Tensor


def _pallas_norms():
    """Fused Pallas norm kernels — OPT-IN via
    ``PDTPU_NORM_BACKEND=pallas``. Read in context in rounds 1-5, at
    GPT-124M width (no cell holds the figures: a cell that turns it on
    would): the Pallas LN custom call is a fusion BARRIER — its input
    and output must materialize in HBM — and the step was slower with
    it than with the jnp formulation, which XLA fuses into the
    neighboring residual-add/cast chains. The same
    isolated-vs-in-context trap as the flash-attention block autotune:
    the kernel wins alone and loses inside the step."""
    import os
    if jax.default_backend() != "tpu" \
            or os.environ.get("PDTPU_NORM_BACKEND") != "pallas":
        return None
    try:
        from ...ops.pallas import norms
        return norms
    except ImportError:
        return None


def _moments(v, axes):
    v32 = v.astype(jnp.float32)
    mean = jnp.mean(v32, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(v32), axis=axes, keepdims=True) - \
        jnp.square(mean)
    return mean, var


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    n_axes = len(tuple(normalized_shape))

    def impl(v, *wb):
        axes = tuple(range(v.ndim - n_axes, v.ndim))
        pn = _pallas_norms()
        if (pn is not None and n_axes == 1 and weight is not None
                and bias is not None):
            return pn.layer_norm(v, wb[0], wb[1], eps=epsilon)
        mean, var = _moments(v, axes)
        out = (v.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(v.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        # saveable under "transformer_saveable" remat: keeps the normed
        # activation as a residual instead of re-reducing in backward
        from jax.ad_checkpoint import checkpoint_name
        return checkpoint_name(out, "ln_out")

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply("layer_norm", impl, *args)


def rms_norm(x, weight=None, bias=None, epsilon=1e-6, begin_norm_axis=-1,
             name=None):
    """RMSNorm (reference fused rms_norm kernel,
    ``paddle/phi/kernels/fusion/gpu/rms_norm_kernel.cu``)."""

    def impl(v, *wb):
        axis = begin_norm_axis if begin_norm_axis >= 0 else v.ndim + begin_norm_axis
        axes = tuple(range(axis, v.ndim))
        pn = _pallas_norms()
        if (pn is not None and axes == (v.ndim - 1,) and weight is not None
                and bias is None):
            return pn.rms_norm(v, wb[0], eps=epsilon)
        v32 = v.astype(jnp.float32)
        ms = jnp.mean(jnp.square(v32), axis=axes, keepdims=True)
        out = (v32 * jax.lax.rsqrt(ms + epsilon)).astype(v.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply("rms_norm", impl, *args)


def fused_residual_norm(x, y, weight, bias=None, epsilon=None,
                        norm="layer", name=None):
    """Fused residual-add + norm glue op (ISSUE 19,
    ``ops.pallas.fused_residual_norm``): returns ``(res, normed)`` with
    ``res = x + y`` (the residual-stream value the next adder consumes)
    and ``normed`` its layer/rms norm — ONE dispatch with a fused
    custom-vjp backward, replacing the separate add and norm ops of the
    training glue chain. ``norm`` selects "layer" (weight+bias) or
    "rms" (weight only). Unlike ``layer_norm``/``rms_norm`` this always
    takes the Pallas kernel path (interpret mode off-TPU); callers gate
    on the ``train_glue_fusion`` flag — see its help for why the fused
    path is an A/B knob rather than a default."""
    if norm not in ("layer", "rms"):
        raise ValueError(f"norm must be 'layer' or 'rms', got {norm!r}")
    if norm == "layer" and bias is None:
        raise ValueError("fused_residual_norm(norm='layer') requires "
                         "bias (LayerNorm's affine pair)")
    eps = epsilon if epsilon is not None else \
        (1e-5 if norm == "layer" else 1e-6)

    def impl(xv, yv, *wb):
        from ...ops.pallas import fused_residual_norm as frn
        if norm == "layer":
            return frn.fused_residual_layer_norm(xv, yv, wb[0], wb[1],
                                                 eps=eps)
        return frn.fused_residual_rms_norm(xv, yv, wb[0], eps=eps)

    args = [x, y] + [t for t in (weight, bias) if t is not None]
    return apply("fused_residual_norm", impl, *args)


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    """Reference ``functional/norm.py`` batch_norm. In training mode the
    running stats buffers are updated in place (host-side assign, matching
    the reference's in-kernel update of mean_out/variance_out)."""
    channel_axis = (1 if data_format.startswith("NC") or x.ndim <= 2
                    else x.ndim - 1)
    if x.ndim <= 2:
        channel_axis = x.ndim - 1
    use_stats = (not training) if use_global_stats is None else use_global_stats

    def impl(v, rm, rv, *wb):
        axes = tuple(a for a in range(v.ndim) if a != channel_axis)
        if use_stats:
            mean = rm.astype(jnp.float32)
            var = rv.astype(jnp.float32)
            bshape = [1] * v.ndim
            bshape[channel_axis] = v.shape[channel_axis]
            mean = mean.reshape(bshape)
            var = var.reshape(bshape)
        else:
            mean, var = _moments(v, axes)
        out = (v.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(v.dtype)
        bshape = [1] * v.ndim
        bshape[channel_axis] = v.shape[channel_axis]
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [x, running_mean, running_var] + \
        [t for t in (weight, bias) if t is not None]
    out = apply("batch_norm", impl, *args)

    if training and not use_stats:
        # update running stats (unbiased variance, matching reference)
        val = x._read() if isinstance(x, Tensor) else x
        axes = tuple(a for a in range(val.ndim) if a != channel_axis)
        n = float(np.prod([val.shape[a] for a in axes]))
        m32 = jnp.mean(val.astype(jnp.float32), axis=axes)
        v32 = jnp.var(val.astype(jnp.float32), axis=axes)
        if n > 1:
            v32 = v32 * (n / (n - 1))
        rm, rv = running_mean, running_var
        rm_val = rm._read() if isinstance(rm, Tensor) else rm
        rv_val = rv._read() if isinstance(rv, Tensor) else rv
        new_m = momentum * rm_val + (1 - momentum) * m32.astype(rm_val.dtype)
        new_v = momentum * rv_val + (1 - momentum) * v32.astype(rv_val.dtype)
        if isinstance(rm, Tensor):
            rm._write(new_m)
            rv._write(new_v)
    return out


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    channel_axis = 1 if data_format.startswith("NC") else x.ndim - 1

    def impl(v, *wb):
        axes = tuple(a for a in range(v.ndim)
                     if a != channel_axis and a != 0)
        mean, var = _moments(v, axes)
        out = (v.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
        out = out.astype(v.dtype)
        bshape = [1] * v.ndim
        bshape[channel_axis] = v.shape[channel_axis]
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply("instance_norm", impl, *args)


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    channel_last = not data_format.startswith("NC")

    def impl(v, *wb):
        if channel_last:
            perm = (0, v.ndim - 1) + tuple(range(1, v.ndim - 1))
            v_t = jnp.transpose(v, perm)
        else:
            v_t = v
        n, c = v_t.shape[0], v_t.shape[1]
        rest = v_t.shape[2:]
        g = v_t.reshape((n, num_groups, c // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        mean, var = _moments(g, axes)
        out = (g.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(v.dtype).reshape(v_t.shape)
        bshape = [1, c] + [1] * (v_t.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(bshape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(bshape)
        if channel_last:
            inv = (0,) + tuple(range(2, v.ndim)) + (1,)
            out = jnp.transpose(out, inv)
        return out

    args = [x] + [t for t in (weight, bias) if t is not None]
    return apply("group_norm", impl, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    def impl(v):
        channel_axis = 1 if data_format.startswith("NC") else v.ndim - 1
        sq = jnp.square(v.astype(jnp.float32))
        pad_lo = (size - 1) // 2
        pad_hi = size - 1 - pad_lo
        win = [1] * v.ndim
        win[channel_axis] = size
        pads = [(0, 0)] * v.ndim
        pads[channel_axis] = (pad_lo, pad_hi)
        s = jax.lax.reduce_window(sq, 0.0, jax.lax.add, tuple(win),
                                  (1,) * v.ndim, pads)
        div = jnp.power(k + alpha * s, beta)
        return (v.astype(jnp.float32) / div).astype(v.dtype)

    return apply("local_response_norm", impl, x)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    """Reference ``spectral_norm`` op
    (``python/paddle/static/nn/common.py`` spectral_norm;
    ``paddle/phi/kernels/impl/spectral_norm_kernel_impl.h``): normalize a
    weight by its largest singular value, estimated with ``power_iters``
    rounds of power iteration on W reshaped to [shape[dim], -1].

    Deterministic u/v start vectors keep the op functional — the
    reference keeps persistent randomly-initialized U/V buffers; the
    layer wrapper owns those here. The start vector is a fixed-key
    Gaussian draw rather than all-ones: an all-ones start is exactly
    orthogonal to any zero-sum left-singular vector (common in centered
    weights), which would converge power iteration to a smaller singular
    value and under-normalize."""
    import jax
    import jax.numpy as jnp

    from ...core.dispatch import apply

    def impl(w):
        d = dim if dim >= 0 else w.ndim + dim
        perm = [d] + [i for i in range(w.ndim) if i != d]
        mat = jnp.transpose(w, perm).reshape(w.shape[d], -1)
        h, wdim = mat.shape
        u = jax.random.normal(jax.random.PRNGKey(0), (h,), jnp.float32)
        u = u / (jnp.linalg.norm(u) + eps)
        v = None
        m = mat.astype(jnp.float32)
        for _ in range(max(1, int(power_iters))):
            v = m.T @ u
            v = v / (jnp.linalg.norm(v) + eps)
            u = m @ v
            u = u / (jnp.linalg.norm(u) + eps)
        sigma = u @ (m @ v)
        out = (m / jnp.maximum(sigma, eps)).astype(w.dtype)
        inv = [perm.index(i) for i in range(w.ndim)]
        return jnp.transpose(
            out.reshape([w.shape[p] for p in perm]), inv)

    return apply("spectral_norm", impl, weight)
