"""Convolution functionals over ``jax.lax.conv_general_dilated``.

Analog of ``python/paddle/nn/functional/conv.py`` (reference; kernels
``paddle/phi/kernels/gpu/conv_kernel.cu`` via cudnn). TPU-native: one XLA
convolution primitive covers conv1d/2d/3d, grouped, dilated and transposed
convs; XLA lays it out for the MXU (no im2col / algo-search machinery needed).
Weights use paddle's [out_c, in_c/groups, *k] layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import apply


def _tuplize(v, n):
    if isinstance(v, int):
        return (v,) * n
    v = tuple(int(x) for x in v)
    if len(v) == 1:
        return v * n
    return v


def _norm_padding(padding, n, strides, dilations, kernel):
    """Returns (list of (lo, hi) per spatial dim) or the string 'SAME'."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return [(0, 0)] * n
        if p == "SAME":
            return "SAME"
        raise ValueError(f"bad padding {padding}")
    if isinstance(padding, int):
        return [(padding, padding)] * n
    padding = list(np.asarray(padding).ravel())
    if len(padding) == n:
        return [(int(p), int(p)) for p in padding]
    if len(padding) == 2 * n:
        return [(int(padding[2 * i]), int(padding[2 * i + 1]))
                for i in range(n)]
    raise ValueError(f"bad padding {padding}")


def _dim_numbers(nd, channel_last):
    if nd == 1:
        return ("NWC", "OIW", "NWC") if channel_last else ("NCW", "OIW", "NCW")
    if nd == 2:
        return (("NHWC", "OIHW", "NHWC") if channel_last
                else ("NCHW", "OIHW", "NCHW"))
    return (("NDHWC", "OIDHW", "NDHWC") if channel_last
            else ("NCDHW", "OIDHW", "NCDHW"))


def _conv_impl(x, weight, bias, strides, padding, dilations, groups,
               channel_last, nd):
    dn = _dim_numbers(nd, channel_last)
    y = jax.lax.conv_general_dilated(
        x, weight, window_strides=strides, padding=padding,
        rhs_dilation=dilations, feature_group_count=groups,
        dimension_numbers=dn,
        preferred_element_type=x.dtype)
    if bias is not None:
        shape = [1] * y.ndim
        shape[-1 if channel_last else 1] = bias.shape[0]
        y = y + bias.reshape(shape)
    return y


def _conv(name, x, weight, bias, stride, padding, dilation, groups,
          data_format, nd):
    strides = _tuplize(stride, nd)
    dilations = _tuplize(dilation, nd)
    channel_last = data_format.endswith("C")
    kernel = weight.shape[2:]
    pad = _norm_padding(padding, nd, strides, dilations, kernel)
    args = (x, weight) if bias is None else (x, weight, bias)

    def impl(x_, w_, b_=None):
        return _conv_impl(x_, w_, b_, strides, pad, dilations, int(groups),
                          channel_last, nd)

    return apply(name, impl, *args)


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCL", name=None):
    fmt = "NWC" if data_format in ("NLC", "NWC") else "NCW"
    return _conv("conv1d", x, weight, bias, stride, padding, dilation,
                 groups, fmt, 1)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCHW", name=None):
    return _conv("conv2d", x, weight, bias, stride, padding, dilation,
                 groups, data_format, 2)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1,
           data_format="NCDHW", name=None):
    return _conv("conv3d", x, weight, bias, stride, padding, dilation,
                 groups, data_format, 3)


def _conv_transpose(name, x, weight, bias, stride, padding, output_padding,
                    dilation, groups, data_format, nd, output_size=None):
    strides = _tuplize(stride, nd)
    dilations = _tuplize(dilation, nd)
    channel_last = data_format.endswith("C")
    kernel = [int(k) for k in weight.shape[2:]]
    pad = _norm_padding(padding, nd, strides, dilations, kernel)
    if pad == "SAME":
        raise NotImplementedError("SAME padding for conv_transpose")
    opad = _tuplize(output_padding or 0, nd)
    # grad-of-conv formulation: lhs_dilation = stride, padding adjusted
    trans_pad = []
    for i in range(nd):
        k_eff = dilations[i] * (kernel[i] - 1) + 1
        lo = k_eff - 1 - pad[i][0]
        hi = k_eff - 1 - pad[i][1] + opad[i]
        trans_pad.append((lo, hi))

    dn = _dim_numbers(nd, channel_last)
    g = int(groups)

    def impl(x_, w_, b_=None):
        # weight layout [in_c, out_c/groups, *k] for paddle conv_transpose;
        # flip spatial dims and swap io for the dilated-conv formulation.
        w = jnp.flip(w_, axis=tuple(range(2, w_.ndim)))
        if g > 1:
            ic, ocg = w.shape[0], w.shape[1]
            w = w.reshape((g, ic // g) + w.shape[1:])
            w = jnp.swapaxes(w, 1, 2)
            w = w.reshape((g * ocg, ic // g) + w.shape[3:])
        else:
            w = jnp.swapaxes(w, 0, 1)
        y = jax.lax.conv_general_dilated(
            x_, w, window_strides=(1,) * nd, padding=trans_pad,
            lhs_dilation=strides, rhs_dilation=dilations,
            feature_group_count=g, dimension_numbers=dn,
            preferred_element_type=x_.dtype)
        if b_ is not None:
            shape = [1] * y.ndim
            shape[-1 if channel_last else 1] = b_.shape[0]
            y = y + b_.reshape(shape)
        return y

    args = (x, weight) if bias is None else (x, weight, bias)
    out = apply(name, impl, *args)
    if output_size is not None:
        want = ([int(s) for s in output_size]
                if not isinstance(output_size, int)
                else [int(output_size)] * nd)
        got = out.shape[2:] if not channel_last else out.shape[1:-1]
        if list(got) != want:
            raise ValueError(
                f"output_size {want} unreachable, got {list(got)}; adjust "
                "output_padding")
    return out


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    fmt = "NWC" if data_format in ("NLC", "NWC") else "NCW"
    return _conv_transpose("conv1d_transpose", x, weight, bias, stride,
                           padding, output_padding, dilation, groups, fmt, 1,
                           output_size)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCHW", name=None):
    return _conv_transpose("conv2d_transpose", x, weight, bias, stride,
                           padding, output_padding, dilation, groups,
                           data_format, 2, output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _conv_transpose("conv3d_transpose", x, weight, bias, stride,
                           padding, output_padding, dilation, groups,
                           data_format, 3, output_size)


def causal_depthwise_conv1d(x, weight, name=None):
    """Depthwise causal convolution over the sequence, channels last:
    ``out[b, t, c] = sum_j weight[j, c] * x[b, t - (K - 1) + j, c]``, zeros
    left of the sequence.  ``x`` is [batch, seq, channels], ``weight``
    [K, channels] (a ``Conv1d(groups=channels, padding=K - 1)`` cut to
    the sequence holds the same taps as [channels, 1, K]).

    Computed as K shifted multiply-adds accumulated in float32: with a
    short kernel (K = 3 in the gated short-conv operator of
    ``models/lfm2.py``) that is one elementwise fusion over lanes of
    channels, forward and backward.  On the v5e at [2, 8192, 2048]
    bfloat16, forward + backward: 2.55 ms against 3.45 ms for
    ``conv1d(groups=channels)``, which asks the chip's convolution
    unit for a feature group per channel (PERF.md, PR 28)."""

    def impl(v, w):
        k, s = w.shape[0], v.shape[1]
        padded = jnp.pad(v.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        out = padded[:, k - 1:] * w[k - 1]
        for j in range(k - 1):
            out = out + padded[:, j:j + s] * w[j]
        return out.astype(v.dtype)

    return apply("causal_depthwise_conv1d", impl, x, weight)
