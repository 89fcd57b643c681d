"""Fused rotary position embedding (RoPE) Pallas kernel.

Capability analog of the reference fused-rope CUDA kernel
(``paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu``, python surface
``paddle.incubate.nn.functional.fused_rotary_position_embedding``): applies
cos/sin rotation to q (and optionally k, v) in one pass, half-rotate
("neox") or interleaved pairing, without materializing the rotated halves
in HBM. RoPE is a linear map whose transpose is the rotation by -theta, so
the backward reuses the same kernel with negated sin.

The interleaved pairing is computed with lane rolls + a parity mask (a
minor-dim reshape/stack does not lower through Mosaic).

``half_turn`` is the training path's kernel at head width 128
(``models/lfm2.py`` ``_rotate``): tiled over positions, so that it holds
a few MB of VMEM at any sequence length; it reads the projection's
[B, S, H * D] rows and writes the attention kernels' [B, H, S, D]; the
half-turn is a lane rotate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, use_neox):
    x = x_ref[0, 0].astype(jnp.float32)        # [S, D]
    cos = cos_ref[0].astype(jnp.float32)       # [S, D]
    sin = sin_ref[0].astype(jnp.float32)
    d = x.shape[-1]
    if use_neox:
        # pair (i, i + d/2): rotate_half
        x1 = x[:, : d // 2]
        x2 = x[:, d // 2:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
    else:
        # pair (2i, 2i+1): rot[2i] = -x[2i+1], rot[2i+1] = x[2i]
        nxt = pltpu.roll(x, d - 1, 1)          # nxt[i] = x[i+1]
        prv = pltpu.roll(x, 1, 1)              # prv[i] = x[i-1]
        even = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) % 2 == 0
        rot = jnp.where(even, -nxt, prv)
    o_ref[0, 0] = (x * cos + rot * sin).astype(o_ref.dtype)


def _rope_call(x, cos, sin, use_neox, interpret):
    """x: [B, H, S, D]; cos/sin: [S, D] or [B, S, D] (per-batch tables,
    e.g. gathered by position_ids) -> same-shape rotated x."""
    b, h, s, d = x.shape
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    batched = cos.shape[0] != 1
    tab_ix = (lambda ib, ih: (ib, 0, 0)) if batched \
        else (lambda ib, ih: (0, 0, 0))
    kernel = functools.partial(_rope_kernel, use_neox=use_neox)
    return pl.pallas_call(
        kernel,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, s, d), lambda ib, ih: (ib, ih, 0, 0)),
            pl.BlockSpec((1, s, d), tab_ix),
            pl.BlockSpec((1, s, d), tab_ix),
        ],
        out_specs=pl.BlockSpec((1, 1, s, d), lambda ib, ih: (ib, ih, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, cos, sin)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _rope_bhsd(x, cos, sin, use_neox, interpret):
    return _rope_call(x, cos, sin, use_neox, interpret)


def _rope_fwd(x, cos, sin, use_neox, interpret):
    return _rope_call(x, cos, sin, use_neox, interpret), (cos, sin)


def _rope_bwd(use_neox, interpret, res, g):
    cos, sin = res
    # transpose of rotation(theta) = rotation(-theta)
    return _rope_call(g, cos, -sin, use_neox, interpret), None, None


_rope_bhsd.defvjp(_rope_fwd, _rope_bwd)


def apply_rope(x, cos, sin, use_neox=True, interpret=None):
    """Rotary embedding in paddle layout [batch, seq, num_heads, head_dim].

    cos/sin: [seq, head_dim] — or [batch, seq, head_dim] for per-example
    position tables — tiled to full head_dim (for ``use_neox=True``:
    ``cos[s, i] = cos(s * inv_freq[i % (d/2)])``; for interleaved:
    ``inv_freq[i // 2]``).
    """
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    xt = jnp.swapaxes(x, 1, 2)
    o = _rope_bhsd(xt, cos.astype(jnp.float32), sin.astype(jnp.float32),
                   bool(use_neox), bool(interpret))
    return jnp.swapaxes(o, 1, 2)


# ------------------------------------------- the half turn, position-tiled
HEAD = 128          # the head width the kernel takes: one vreg's lanes
# the block, from a sweep on the v5e at [1, 8192, 64, 128] (PERF.md,
# PR 45): 256-1024 positions x 8-16 heads lie within 2% of each other
# at 76% of HBM's speed, 4 heads and 16 rows a pass read 5-25% slower,
# 1024 x 16 and 2048 x 8 outgrow the VMEM a kernel may use
BLOCK_S = 512       # positions a block
BLOCK_H = 8         # heads a block: 2 KB of every position's row
_ROWS = 64          # positions a pass of the body, its tables' rows read once

# (cos, sin) by identity -> (cos, sin, C, S, S^T): a stack's layers share
# a layer type's tables, so they share these and the program holds each
# constant once.  The key's arrays are kept, so an id is not met again.
_TURN_TABLES = {}


def turn_tables(cos, sin):
    """``C, S, St`` float32 [positions, 128] from half-rotation tables
    [positions, r], r <= 128 and even, made on the host: ``C`` is
    ``cos`` widened to the head with ones, ``S`` is ``-sin`` on the
    lanes below ``r / 2``, ``sin`` on the lanes from there to ``r`` and
    zero beyond, so that ``x * C + swap(x) * S`` turns the first ``r``
    dimensions of a head and passes the rest (``swap`` exchanges the
    two halves of those ``r``); ``St`` is ``swap(S)``, the transposed
    map's table: ``g * C + swap(g) * St``.  Whatever scale the tables
    carry (YaRN's ``attention_factor``) is inside all three."""
    import numpy as np
    key = (id(cos), id(sin))
    if key not in _TURN_TABLES:
        c, s = np.asarray(cos, np.float32), np.asarray(sin, np.float32)
        n, r = c.shape
        if r % 2 or not 0 < r <= HEAD:
            raise ValueError(f"tables {r} wide turn halves of a head of "
                             f"{HEAD}")
        pad = np.zeros((n, HEAD - r), np.float32)
        big_c = np.concatenate([c, pad + 1], axis=-1)
        big_s = np.concatenate([-s[:, :r // 2], s[:, r // 2:], pad], axis=-1)
        big_st = np.concatenate([s[:, r // 2:], -s[:, :r // 2], pad], axis=-1)
        with jax.ensure_compile_time_eval():
            _TURN_TABLES[key] = (cos, sin, jnp.asarray(big_c),
                                 jnp.asarray(big_s), jnp.asarray(big_st))
    return _TURN_TABLES[key][2:]


def _turn_kernel(x_ref, c_ref, s_ref, o_ref, *, r, heads, rows):
    """One block of positions of some heads.  A ref is [1, bs, heads *
    128], positions by the heads' lanes as a projection writes them, or
    [1, heads, bs, 128], head-major as the attention kernels read;
    c_ref, s_ref [bs, 128]."""

    def head(ref, at, h):
        if len(ref.shape) == 4:
            return (0, h, at, slice(None))
        return (0, at, pl.ds(h * HEAD, HEAD))

    def some_rows(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        c, s = c_ref[at, :], s_ref[at, :]
        low = jax.lax.broadcasted_iota(jnp.int32, c.shape, 1) < r // 2
        for h in range(heads):
            x = x_ref[head(x_ref, at, h)].astype(jnp.float32)
            if r == HEAD:
                swapped = pltpu.roll(x, HEAD // 2, 1)
            else:
                # beyond lane r the tables pass x through: S is zero
                swapped = jnp.where(low, pltpu.roll(x, HEAD - r // 2, 1),
                                    pltpu.roll(x, r // 2, 1))
            o_ref[head(o_ref, at, h)] = (x * c + swapped * s).astype(
                o_ref.dtype)

    jax.lax.fori_loop(0, c_ref.shape[0] // rows, some_rows, None)


def _turn_call(x, c, s, r, backward, interpret):
    """Forward: ``x`` [B, S, H, 128] read through its [B, S, H * 128]
    view, which is how a projection's result lies on the chip, and the
    result [B, H, S, 128], which is how the attention kernels read it:
    the layout's change costs no pass of its own.  Backward the other
    way round, on the cotangent."""
    from . import out_struct
    if backward:
        b, h, n, d = x.shape
    else:
        b, n, h, d = x.shape
    bs = min(BLOCK_S, n)
    hb = max(m for m in range(1, BLOCK_H + 1) if h % m == 0)
    wide = pl.BlockSpec((1, bs, hb * d), lambda ib, i, ih: (ib, i, ih))
    major = pl.BlockSpec((1, hb, bs, d), lambda ib, i, ih: (ib, ih, i, 0))
    table = pl.BlockSpec((bs, d), lambda ib, i, ih: (i, 0))
    # heads innermost: a block of positions fetches its tables once
    out = pl.pallas_call(
        functools.partial(_turn_kernel, r=r, heads=hb,
                          rows=_ROWS if bs % _ROWS == 0 else bs),
        grid=(b, pl.cdiv(n, bs), h // hb),
        in_specs=[major if backward else wide, table, table],
        out_specs=wide if backward else major,
        out_shape=out_struct((b, n, h * d) if backward else (b, h, n, d),
                             x.dtype, x),
        name="rope_half_turn_bwd" if backward else "rope_half_turn_fwd",
        interpret=interpret,
    )(x if backward else x.reshape(b, n, h * d), c, s)
    return out.reshape(b, n, h, d) if backward else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _half_turn(x, c, s, st, r, interpret):
    return _turn_call(x, c, s, r, False, interpret)


def _half_turn_fwd(x, c, s, st, r, interpret):
    return _turn_call(x, c, s, r, False, interpret), (c, st)


def _half_turn_bwd(r, interpret, tables, g):
    # the map is linear and, with YaRN's factor, not orthogonal: its
    # transpose, which is the same body over the swapped table
    c, st = tables
    return _turn_call(g, c, st, r, True, interpret), None, None, None


_half_turn.defvjp(_half_turn_fwd, _half_turn_bwd)


def half_turn(x, cos, sin, interpret=None):
    """Half-rotation RoPE of ``x`` [batch, seq, heads, 128] by float32
    tables [seq, r] (r <= 128: the head's first ``r`` dimensions turn,
    the rest pass), float32 inside and one rounding to ``x``'s type at
    the store.  One pass over ``x`` forward and one over its cotangent
    backward, each a kernel; nothing but the tables is kept between."""
    if x.shape[-1] != HEAD or cos.shape[0] != x.shape[1]:
        raise ValueError(f"half_turn: x {x.shape}, tables {cos.shape}")
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    # head-major from the kernel; the attention wrapper's own swapaxes
    # undoes this one, and XLA drops the pair
    return jnp.swapaxes(_half_turn(x, *turn_tables(cos, sin), cos.shape[-1],
                                   bool(interpret)), 1, 2)
