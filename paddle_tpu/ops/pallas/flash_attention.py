"""Pallas TPU flash attention (forward + backward, causal + GQA).

Capability analog of the reference FlashAttention-2 integration
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu:91`` fwd,
``flash_attn_grad_kernel.cu`` bwd, python surface
``python/paddle/nn/functional/flash_attention.py:147``) — TPU-native design:

* online-softmax tiling sized for the MXU (q blocks x k blocks): all
  seven products (two forward, five backward) take q, k, v, do as the
  tensors hold them and ``p`` / ``ds`` cast to that dtype, and accumulate
  in float32; float32 inputs stay float32; the row statistics, ``exp``,
  the accumulators and ``delta`` are float32 always.  ``q * scale`` is
  rounded once to the inputs' dtype (``_scaled``).  On the chip a
  float32 product in a kernel is one bf16 pass too (measured, PR 32: the
  same gradients to the last digit), so this buys no MXU time; it
  halves the width of ``p`` / ``ds``, which a 1024 x 1024 backward tile
  repays with 7%;
* per-(batch, head) grid programs keep K/V resident in VMEM while a q block
  streams through — no [S, S] score matrix ever exists in HBM;
* a tile does the work that tile needs: causal programs stop the k loop at
  the diagonal block (the FA2 trick that halves causal FLOPs); the
  backward runs the tiles wholly under the diagonal, inside the lengths
  and free of segment ids through a body with no iota, compare or
  ``where`` and skips those wholly above it; the forward builds a mask
  only in a call some tile of which can need one (one body a call: a
  mask-free loop beside the masked one was slower on the chip).
  ``_fwd_k_blocks`` / ``_fwd_masks`` / ``_bwd_tile_kinds`` decide it for
  the kernels, the twin and the ``flash.tiles{kernel, kind, shape}``
  gauges of the ``observability`` registry alike;
* a sliding ``window`` (causal calls only: query ``i`` sees the
  ``window`` keys ``i - window < j <= i``) gives the band a lower edge:
  the forward's k loop STARTS at the first block that holds a visible
  key (``_fwd_k_first``), and the backward's grid walks, for each k
  block, only the q blocks of that block's band (``_bwd_q_first`` /
  ``_bwd_q_steps``), masking the tiles either edge crosses.  A windowed
  call's two kernels are named ``flash_window_fwd`` / ``flash_window_bwd``
  in a device trace; ``window=None`` and a window no shorter than the
  keys build the programs they built before there was one;
* grouped-query attention maps q-head -> kv-head in the BlockSpec index map
  (no materialized ``repeat`` of K/V, unlike the XLA fallback);
* backward recomputes the softmax from the saved logsumexp (flash-attn
  recompute strategy) in ONE fused kernel: a 4-D grid walks (k-block,
  q-block) tiles, recomputing the attention probabilities ONCE per tile
  and producing dk/dv (VMEM accumulators over the q grid dim, held
  transposed so that no [bq, bk] operand has to be turned) AND dq (a
  persistent full-row VMEM scratch accumulated over the k grid dim) from
  the same ``p``/``ds`` — the previous two-pass backward paid the s/p
  recompute twice (7 tile dots; fused is 5, the ~2.5x-over-forward FLOP
  ideal instead of the measured 4.5x).

Parity discipline (the ``quant_matmul_jnp`` contract):
``flash_attention_bwd_jnp`` is an UNJITTED jnp twin replaying the fused
kernel's exact tile walk — same per-tile dot shapes, same operand casts,
same tile kinds, same accumulate order, same masks — so Pallas-interpret
backward grads are BITWISE equal to the twin on CPU for every geometry
(causal x GQA x segment-ids x padded tails).  Gradients leave the kernel
in the caller's dtype (dq always; dk and dv unless a GQA group is summed
outside, which gets them float32).  Backward block sizes are tuned
separately from the forward under the ``flash_attention_bwd`` autotune
entry (the backward's VMEM footprint — full-row q/do/dq buffers plus the
k-tile accumulators — admits different winners than the forward).

Public entry: ``flash_attention(q, k, v, causal=..., scale=...)`` in
paddle's [batch, seq, num_heads, head_dim] layout, differentiable via
``jax.custom_vjp``.  ``k``'s head_dim is ``q``'s (the scores contract over
it); ``v``'s is its own: ``o``, ``do`` and ``dv`` are as wide as ``v``,
``dq`` and ``dk`` as wide as ``q`` (latent attention: 192-wide keys, 128-wide
values).  Every tensor keeps its own width in HBM; a width that is no
multiple of the 128 lanes is padded by Mosaic inside VMEM only.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free
_LANE = 8  # trailing lane width for per-row stats (Mosaic tile alignment)


def _block_sizes(sq, sk, causal, window=None):
    """Default forward (block_q, block_k): 512 x 512 whatever ``causal``
    (a windowed call's: ``_window_block_sizes``).
    Measured 2026-09-29 on one TPU v5e (PR 32; the kernel alone in a
    jitted scan of grad calls under the profiler, bfloat16, ms a call,
    this kernel / the one before the PR at 512 x 512):
    causal 8 x 16 x 1024 x 64 (GPT-2-medium) 0.581 / 0.601, against
    256 x 512 0.603, 1024 x 1024 0.635, 1024 x 512 0.675, 256 x 256
    0.839, 128 x 128 1.916; causal 2 x 32/8 x 8192 x 64 (LFM2) 9.60 /
    9.89, against 256 x 1024 9.74, 512 x 1024 9.89, 1024 x 1024 10.16,
    512 x 2048 10.92, 512 x 256 15.02; full 16 x 16 x 512 x 64
    (BERT-large) 0.355 / 0.352, against 256 x 512 0.500, 256 x 256
    0.665.  Narrow k blocks lose most: the per-step rescale of the
    accumulator and the lane-sparse row statistics are paid per k block.
    What did NOT pay there: a mask-free loop over the tiles wholly
    under the diagonal beside the masked one made the forward 11%
    (1,024 positions) and 4% (8,192) slower, so it has one body."""
    if window is not None:
        return _window_block_sizes(sq, sk)[0]
    return min(512, sq), min(512, sk)


def _window_block_sizes(sq, sk):
    """A windowed call's default ((forward block_q, block_k), (backward
    block_q, block_k)), whatever its window.  A q block of ``bq`` rows
    sees a band of ``bq + window - 1`` keys, so a tile's masked share
    grows with the blocks while the per-block costs of ``_block_sizes``
    fall with them.  THE RULE: 512 x 512 both, at every window: the
    readings at 1,024 keys and at 512 chose the same pair but for the
    forward's q block, where 256 x 512 ties at 1,024 and read 1.2-1.4%
    under 512 x 512 at 512 keys (0.05 ms a call, 0.15 ms of a 370 ms
    step: no step-level pair of runs could show it, so no fork); a key
    block stays 512 wide however narrow the window, because halving it
    cost more per block (the accumulator's rescale, the row statistics,
    the grid step) than the masked area it saved, at both windows, and
    the backward loses with either block halved.
    Measured on one TPU v5e as in ``_block_sizes`` (bfloat16, ms a
    call).  2026-10-02 (PR 42) at 1 x 32/4 x 8192 x 128, a
    window of 1024.  Forward: 512 x 512 2.32, 256 x 512 2.32, 256 x 1024
    2.89, 512 x 1024 2.95, 1024 x 1024 2.95, 1024 x 512 2.98, 128 x 512
    3.06, 512 x 256 3.21, 256 x 256 3.23 (the plain causal call at that
    shape 5.21: a band of 0.234 of its pairs in 0.45 of its time; at
    512 x 512 a q block visits three key blocks of which two are half
    behind an edge).  Backward (the forward at 512 x 512 taken out):
    512 x 512 5.24, 256 x 512 5.92, 512 x 256 6.40, 1024 x 512 6.46,
    256 x 1024 6.59, 512 x 1024 6.63, 256 x 256 6.74, 1024 x 1024 6.76,
    1024 x 256 7.34 (plain causal at 1024 x 1024: 10.77).  The plain
    backward's 1024 x 1024 loses here: two of its every two tiles are
    crossed by an edge.
    2026-10-03 (PR 44) at 1 x 64/8 x 8192 x 128, a window of 512, where
    at 512 x 512 a q block's band of 1,023 keys is exactly two key
    blocks and an edge cuts both (31 tiles a head, none whole: twice
    the band's pairs are visited, as at 256 x 512; at 256 x 256 one
    tile in three is whole and 1.5 times are).  Forward: 256 x 512 3.82
    (again, in turn with 512 x 512: 3.820 / 3.867, 3.833 / 3.874,
    3.835 / 3.883), 512 x 512 3.88, 256 x 256 4.73, 128 x 512 4.84,
    256 x 1024 4.94, 1024 x 512 5.03, 512 x 256 5.08, 128 x 256 6.35,
    256 x 128 7.30, 128 x 128 9.03.  Backward (a forward at 256 x 512
    taken out): 512 x 512 9.08, 256 x 512 9.99, 256 x 256 10.34,
    512 x 256 10.60, 1024 x 512 11.19, 128 x 512 12.38, 256 x 128 12.70,
    1024 x 256 12.71, 128 x 256 13.88 (the plain causal pair at 1 x 48/8
    x 8192 x 128, its defaults: 7.88 and 16.16).  The band is 0.121 of
    a causal layer's pairs and the pair takes 0.40 of a causal pair's
    time a head.  A window under 512 keys has no reading."""
    return ((min(512, sq), min(512, sk)), (min(512, sq), min(512, sk)))


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _scaled(x, scale):
    """``x * scale`` rounded ONCE to x's dtype, the operand both score
    products take.  Exact for float32, and for bfloat16 where the scale
    is a power of two (head_dim 64: 1/8); elsewhere (head_dim 128) each
    element of q moves by at most half a bfloat16 ulp, 2**-8 of itself
    at most: the size of the rounding the caller's q already carries."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


# --------------------------------------------------------------------------
# which tiles need what: ONE place decides the kernels' loop bounds and
# predicates, the twin's walk and the ``flash.tiles`` gauges.  Each
# function takes traced int32 scalars (inside a kernel) or numpy index
# arrays (counting, at trace time) and does the same integer arithmetic.
# --------------------------------------------------------------------------
def _fwd_k_blocks(iq, *, causal, sq, sk, bq, bk):
    """How many k blocks q block ``iq`` visits: those the block's LAST
    row can see; the rest lie above the diagonal and are skipped."""
    xp = np if isinstance(iq, np.ndarray) else jnp
    nk = -(-sk // bk)
    if not causal:
        return xp.full_like(iq, nk)
    return xp.clip(((iq + 1) * bq + (sk - sq) + bk - 1) // bk, 0, nk)


def _fwd_k_first(iq, *, window, sq, sk, bq, bk):
    """The first k block q block ``iq`` visits: the one that holds the
    oldest key the block's FIRST row sees through the window (0 without
    a window); the blocks before it lie wholly behind the window."""
    if window is None:
        return 0 if not isinstance(iq, np.ndarray) else np.zeros_like(iq)
    xp = np if isinstance(iq, np.ndarray) else jnp
    return xp.clip((iq * bq + (sk - sq) - window + 1) // bk, 0, -(-sk // bk))


def _fwd_masks(*, causal, has_seg, sk, bk):
    """Whether the forward's tile body builds a mask: it does when some
    tile of the call can need one (the diagonal, a padded k tail,
    segment ids).  One body for every visited tile: the forward is not
    bound by its vector work, and a mask-free loop beside the masked one
    made it 4-11% slower on the chip (`_block_sizes`)."""
    return bool(causal or has_seg or sk % bk)


def _bwd_tile_kinds(ik, iq, *, causal, has_seg, sq, sk, bq, bk,
                    window=None):
    """(plain, masked) for the backward's (k block, q block) tile; a
    tile that is neither is skipped: it lies wholly above the diagonal
    or wholly behind the ``window``.  Each is ``True`` / ``False``
    where the shapes alone decide it for every tile of the call."""
    offset = sk - sq
    # the tile's last row sees its first column
    active = ((iq + 1) * bq - 1 + offset >= ik * bk) if causal else True
    if window is not None:
        # the tile's first row still sees its last column
        active = active & (iq * bq + offset - (ik + 1) * bk + 1 < window)
    if has_seg:
        return False, active
    # the tile's first row sees its last column
    plain = ((ik + 1) * bk - 1 <= iq * bq + offset) if causal else True
    if window is not None:
        # the tile's last row still sees its first column
        plain = plain & ((iq + 1) * bq - 1 + offset - ik * bk < window)
    if sq % bq:
        plain = plain & ((iq + 1) * bq <= sq)
    if sk % bk:
        plain = plain & ((ik + 1) * bk <= sk)
    if plain is True:
        return True, False
    return plain, active & ~plain


def _bwd_q_first(ik, *, sq, sk, bq, bk):
    """The first q block of k block ``ik``'s band in a windowed call's
    backward: the one that holds the first row that sees the block's
    first column."""
    xp = np if isinstance(ik, np.ndarray) else jnp
    return xp.maximum((ik * bk - (sk - sq)) // bq, 0)


def _bwd_q_steps(*, window, sq, sk, bq, bk):
    """How many q blocks a windowed call's backward walks for each k
    block: the most that any k block's band of ``bk + window - 1`` rows
    touches."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    first = _bwd_q_first(np.arange(nk), sq=sq, sk=sk, bq=bq, bk=bk)
    last = (np.arange(1, nk + 1) * bk + window - 2 - (sk - sq)) // bq
    return int(np.clip(np.minimum(last, nq - 1) - first + 1, 1, nq).max())


def _shape_sig(q_shape, sk, causal, dv=None, window=None):
    """A call's shape as the autotune cache and the gauges key it; the
    values' width ``dv`` is named only where it is not the keys', the
    ``window`` only where there is one: a windowed and a plain call of
    one shape share neither a tuned entry nor a counter series."""
    b, h, sq, d = q_shape
    width = f"d{d}" if dv in (None, d) else f"d{d}v{dv}"
    band = "" if window is None else f"w{window}"
    return f"b{b}h{h}sq{sq}sk{sk}{width}c{int(causal)}{band}"


def _publish_tiles(kernel, q_shape, dv, sk, causal, has_seg, blocks,
                   window=None, **kinds):
    """Gauges ``flash.tiles{kernel, kind, shape}``: the tiles of each
    kind one call of this program shape runs (batch x heads x a row's),
    set while the call is traced — static counts, nothing in the step.
    ``skipped`` are the tiles no product is made for: those above the
    diagonal and those behind the window."""
    from ...observability import metrics
    shape = (f"{_shape_sig(q_shape, sk, causal, dv, window)}"
             f"s{int(has_seg)}.{blocks[0]}x{blocks[1]}")
    for kind, n in kinds.items():
        metrics.registry().gauge(
            "flash.tiles",
            "score tiles a flash-attention call runs, by what they need",
            labels={"kernel": kernel, "kind": kind, "shape": shape}
        ).set(int(n) * q_shape[0] * q_shape[1])


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, has_seg,
                sq, sk, bq, bk, window=None):
    """One (batch, q-head, q-block) program: stream k/v blocks with online
    softmax. Block shapes: q [1,1,bq,D]; k [1,1,Skp,D]; v [1,1,Skp,Dv];
    o [1,1,bq,Dv]; lse [1,1,bq,LANE] (Mosaic needs the trailing dims
    tile-aligned, so the per-row logsumexp is replicated across a small
    lane axis). With
    ``has_seg``, per-token segment ids (q a COLUMN [1,bq,1], kv a ROW
    [1,1,Skp] — the two layouts the mask compares without a relayout,
    and blocks whose minor dims Mosaic accepts) confine
    attention to same-segment pairs (varlen/packed-sequence support —
    the reference's ``flash_attn_varlen_fwd`` capability)."""
    if has_seg:
        qs_ref, ks_ref, o_ref, lse_ref = refs
    else:
        o_ref, lse_ref = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(2)
    dt = q_ref.dtype                                   # the products' operands
    q = _scaled(q_ref[0, 0], scale)                    # [bq, D]
    offset = sk - sq                                   # causal diagonal shift
    hi = _fwd_k_blocks(iq, causal=causal, sq=sq, sk=sk, bq=bq, bk=bk)
    lo = _fwd_k_first(iq, window=window, sq=sq, sk=sk, bq=bq, bk=bk)
    masked = _fwd_masks(causal=causal, has_seg=has_seg, sk=sk, bk=bk)

    def body(j, carry):
        m_i, l_i, acc = carry
        kb = k_ref[0, 0, pl.ds(j * bk, bk), :]
        vb = v_ref[0, 0, pl.ds(j * bk, bk), :]
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
            mask = cols < sk                           # k padding
            if causal:
                mask = mask & (rows + offset >= cols)
            if window is not None:
                mask = mask & (rows + offset - cols < window)
            if has_seg:
                qs = qs_ref[0]                         # [bq, 1]
                ks = ks_ref[0, :, pl.ds(j * bk, bk)]   # [1, bk]
                mask = mask & (qs == ks)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [bq, bk]
        alpha = jnp.exp(m_i - m_new)                   # [bq, 1]
        l_new = l_i * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(dt), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))

    l_safe = jnp.where(l_f == 0.0, 1.0, l_f)           # padded q rows
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(m_f + jnp.log(l_safe), (bq, _LANE))


def _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks=None,
         window=None):
    """q [B,Hq,Sq,D]; k [B,Hk,Sk,D]; v [B,Hk,Sk,Dv]; seg_q/seg_k
    optional [B,Sq]/[B,Sk] int32 segment ids -> (o [B,Hq,Sq,Dv], lse
    [B,Hq,Sq])."""
    b, hq, sq, d = q.shape
    hk, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = hq // hk
    has_seg = seg_q is not None
    bq, bk = (blocks if blocks is not None
              else _block_sizes(sq, sk, causal, window))
    bq, bk = min(bq, sq), min(bk, sk)
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    sqp, skp = qp.shape[2], kp.shape[2]
    grid = (b, hq, sqp // bq)
    geom = dict(sq=sq, sk=sk, bq=bq, bk=bk)
    blocks_q = np.arange(sqp // bq)
    ran = np.maximum(
        _fwd_k_blocks(blocks_q, causal=causal, **geom)
        - _fwd_k_first(blocks_q, window=window, **geom), 0).sum()
    masked = _fwd_masks(causal=causal, has_seg=has_seg, sk=sk, bk=bk)
    _publish_tiles("fwd", q.shape, dv, sk, causal, has_seg, (bq, bk),
                   window,
                   plain=0 if masked else ran, masked=ran if masked else 0,
                   skipped=(sqp // bq) * (skp // bk) - ran)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_seg=has_seg, window=window, **geom)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, skp, d),
                     lambda ib, ih, iq, _rep=rep: (ib, ih // _rep, 0, 0)),
        pl.BlockSpec((1, 1, skp, dv),
                     lambda ib, ih, iq, _rep=rep: (ib, ih // _rep, 0, 0)),
    ]
    args = [qp, kp, vp]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, bq, 1), lambda ib, ih, iq: (ib, iq, 0)),
            pl.BlockSpec((1, 1, skp), lambda ib, ih, iq: (ib, 0, 0)),
        ]
        args += [_pad_to(seg_q.astype(jnp.int32), 1, bq)[:, :, None],
                 _pad_to(seg_k.astype(jnp.int32), 1, bk)[:, None, :]]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, dv), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, _LANE),
                         lambda ib, ih, iq: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sqp, dv), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sqp, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd" if window is None else "flash_window_fwd",
    )(*args)
    return o[:, :, :sq], lse[:, :, :sq, 0]


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_block_sizes(sq, sk, causal, window=None):
    """Default backward (block_q, block_k): 1024 x 1024 whatever
    ``causal`` (a windowed call's: ``_window_block_sizes``).  Measured
    as in ``_block_sizes`` (ms a call, bfloat16):
    causal 8 x 16 x 1024 x 64: 1024 x 1024 (one tile a row) 0.773,
    1024 x 512 0.972, 512 x 1024 0.977, 512 x 512 1.061, 256 x 512
    1.399, 256 x 256 1.748 (before the PR, 512 x 512: 1.186); causal
    2 x 32/8 x 8192 x 64: 1024 x 1024 16.60, 512 x 1024 17.53,
    1024 x 512 17.88, 512 x 512 18.63, 512 x 2048 18.86, 256 x 1024
    19.07 (before: 23.13); full 16 x 16 x 512 x 64 is one 512 x 512
    tile: 0.598 (before: 0.767).  A larger tile wastes more area above
    the diagonal and still wins: fewer grid steps (at 8,192 and 512 x
    512, 120 of a row's 256 are skipped ones) and more independent work
    for the scheduler inside a step.  The whole-row q/do/dq buffers are
    there regardless of the pair (``_bwd_vmem_limit``)."""
    if window is not None:
        return _window_block_sizes(sq, sk)[1]
    return min(1024, sq), min(1024, sk)


_SCOPED_VMEM = 16 << 20     # what Mosaic gives a kernel unless told
_TILE_VMEM = 8 << 20        # least room for the k/v tiles, p, ds and dp


def _bwd_vmem_limit(sqp, d, itemsize, bq, bk, dv=None):
    """``vmem_limit_bytes`` for the fused backward, or None where the
    default holds it.  The kernel keeps the whole row of q (``d`` wide),
    do (``dv`` wide), the lane-replicated lse and delta (double-buffered
    inputs), dq (a double-buffered output) and the dq accumulator
    resident, each padded to 128 lanes: 4.5 KB a position at head_dim 64
    in bfloat16, so 4.7 MB at 1024 positions and 37.7 MB at 8192, which
    the chip's compiler refuses under the 16 MB default (the v5e has
    128 MiB); 6.5 KB a position at 192-wide keys and 128-wide values
    (q, dq and the accumulator padded to 256 lanes), 54.5 MB at 8192.
    Beside the rows, room for a tile's s, p, dp and ds: 32 bytes a score,
    ``_TILE_VMEM`` up to 512 x 512.  A window changes neither: the rows
    stay whole and a tile is a tile."""
    def lanes(n):
        return -(-n // 128) * 128
    dv = d if dv is None else dv
    rows = sqp * (2 * (lanes(d) + lanes(dv)) * itemsize
                  + 2 * 2 * lanes(_LANE) * 4
                  + 2 * lanes(d) * 4 + lanes(d) * 4)
    need = rows + max(_TILE_VMEM, 32 * bq * bk)
    return None if need <= _SCOPED_VMEM else need


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, scale, causal, has_seg, sq, sk, bq, bk,
                      nq, nk, window=None, steps=None):
    """One (batch, q-head, k-block, q-block) tile of the FUSED backward.

    The grid's two inner dims walk k-blocks (outer) x q-blocks (inner);
    each tile recomputes the attention probabilities ONCE and feeds all
    three gradients from the same ``p``/``ds``:

    - dk/dv accumulate in VMEM scratch over the q dim (re-zeroed at
      ``iq == 0``, flushed to their per-k-block output at
      ``iq == nq - 1`` — the quant_matmul K-grid accumulator pattern);
    - dq accumulates in a PERSISTENT full-row VMEM scratch over the k
      dim (scratch lives across grid steps; each q-row slice is zeroed
      at ``ik == 0`` and flushed to the dq output once its last
      attending k block — ``hi - 1`` — has contributed).

    Causal tiles strictly above the diagonal are predicated off with
    ``pl.when`` (the skip that halves causal backward FLOPs); the
    zero-init/flush bookkeeping runs outside the predicate so padded or
    never-attending rows still produce zeros.  The five products take
    q, k, v, do as the refs hold them and ``p`` / ``ds`` cast to that
    dtype, and accumulate in float32.

    With a ``window`` the grid's last dim has ``steps`` steps, not
    ``nq``: for each k block it walks the q blocks of that block's band
    from ``_bwd_q_first`` on, so no grid step is spent on a tile behind
    the window (at 8,192 positions and a window of 1,024 they would be
    four fifths of the steps).  A q block then meets its k blocks at
    steps the shapes do not fix, so the dq rows are zeroed whole in the
    program's first step and flushed whole in its last.
    """
    if has_seg:
        qs_ref, ks_ref, dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc \
            = refs
    else:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
        qs_ref = ks_ref = None
    ik = pl.program_id(2)
    iq = pl.program_id(3)
    offset = sk - sq
    if window is not None:
        step, last_step = iq, steps - 1
        iq = _bwd_q_first(ik, sq=sq, sk=sk, bq=bq, bk=bk) + step
        inside = iq < nq        # the band of the last k blocks ends early
        iq = jnp.minimum(iq, nq - 1)
    else:
        step, last_step = iq, nq - 1

    @pl.when(step == 0)
    def _zero_kv_acc():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    if window is None:
        @pl.when(ik == 0)
        def _zero_dq_slice():
            dq_acc[pl.ds(iq * bq, bq), :] = jnp.zeros(
                (bq, dq_acc.shape[-1]), jnp.float32)
    else:
        @pl.when((ik == 0) & (step == 0))
        def _zero_dq_rows():
            def zero(i, _):
                dq_acc[pl.ds(i * bq, bq), :] = jnp.zeros(
                    (bq, dq_acc.shape[-1]), jnp.float32)
            jax.lax.fori_loop(0, nq, zero, None)

    dt = q_ref.dtype                                   # the products' operands

    def tile(masked):
        kb = k_ref[0, 0]                               # [bk, D]
        vb = v_ref[0, 0]                               # [bk, Dv]
        qb = q_ref[0, 0, pl.ds(iq * bq, bq), :]        # [bq, D]
        dob = do_ref[0, 0, pl.ds(iq * bq, bq), :]      # [bq, Dv]
        lse = lse_ref[0, 0, pl.ds(iq * bq, bq), 0:1]   # [bq, 1]
        dlt = delta_ref[0, 0, pl.ds(iq * bq, bq), 0:1]
        s = jax.lax.dot_general(
            _scaled(qb, scale), kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        p = jnp.exp(s - lse)                           # recomputed ONCE
        if masked:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
            mask = (cols < sk) & (rows < sq)
            if causal:
                mask = mask & (rows + offset >= cols)
            if window is not None:
                mask = mask & (rows + offset - cols < window)
            if has_seg:
                qs = qs_ref[0, pl.ds(iq * bq, bq), :]  # [bq, 1]
                ks = ks_ref[0, :, pl.ds(ik * bk, bk)]  # [1, bk]
                mask = mask & (qs == ks)
            p = jnp.where(mask, p, 0.0)
        # dv and dk accumulate TRANSPOSED, [Dv, bk] = do^T p and
        # [D, bk] = q^T ds:
        # the operand Mosaic has to turn for a product contracted over
        # rows is then the [bq, D] one, not the [bq, bk] one
        dv_acc[...] += jax.lax.dot_general(
            dob, p.astype(dt), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [Dv, bk]
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = (p * (dp - dlt)).astype(dt)               # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            qb, ds, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [D, bk]
        # dk (above) and dq accumulate UNSCALED, from q and k as the
        # refs hold them: a fused multiply in the accumulate chain
        # FMA-contracts under compilation and drifts the last ulp vs the
        # unjitted twin, and a q scaled and rounded again would cost dk
        # a rounding at head dims whose scale is no power of two; the
        # single scale multiply happens at flush
        dq_acc[pl.ds(iq * bq, bq), :] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # a grid step is plain (wholly under the diagonal, within the
    # window, inside sq and sk, no segment ids: no iota, compare or
    # where), masked, or skipped (wholly above the diagonal or behind
    # the window); a kind no step of this call can have is decided here
    # and its body is not emitted
    plain, masked = _bwd_tile_kinds(ik, iq, causal=causal, has_seg=has_seg,
                                    sq=sq, sk=sk, bq=bq, bk=bk,
                                    window=window)
    for kind, is_masked in ((plain, False), (masked, True)):
        if window is not None and kind is not False:
            kind = inside & kind
        if kind is True:
            tile(is_masked)
        elif kind is not False:
            pl.when(kind)(functools.partial(tile, is_masked))

    if window is not None:
        @pl.when((ik == nk - 1) & (step == last_step))
        def _flush_dq_rows():
            def flush(i, _):
                dq_ref[0, 0, pl.ds(i * bq, bq), :] = \
                    (dq_acc[pl.ds(i * bq, bq), :] * scale).astype(
                        dq_ref.dtype)
            jax.lax.fori_loop(0, nq, flush, None)
    else:
        # flush dq once this q row's LAST attending k block has run. hi
        # can be <= 0 for rows that attend nothing (sq > sk rectangles):
        # clamp to 1 so the zeroed slice still flushes at ik == 0.
        if causal:
            hi = jnp.minimum(nk, ((iq + 1) * bq + offset + bk - 1) // bk)
            hi = jnp.maximum(hi, 1)
        else:
            hi = nk

        @pl.when(ik == hi - 1)
        def _flush_dq():
            dq_ref[0, 0, pl.ds(iq * bq, bq), :] = \
                (dq_acc[pl.ds(iq * bq, bq), :] * scale).astype(dq_ref.dtype)

    @pl.when(step == last_step)
    def _flush_kv():
        dk_ref[0, 0] = (dk_acc[...].T * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].T.astype(dv_ref.dtype)


def _bwd(scale, causal, interpret, blocks, bwd_blocks, window, res, g):
    q, k, v, seg_q, seg_k, o, lse = res
    do = g
    b, hq, sq, d = q.shape
    hk, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = hq // hk
    has_seg = seg_q is not None
    # precedence: explicit bwd_blocks > the forward's (possibly caller-
    # pinned) pair > the measured default — a caller who pinned blocks=
    # gets the pre-split behavior of one pair driving both directions
    bq, bk = (bwd_blocks if bwd_blocks is not None
              else blocks if blocks is not None
              else _bwd_block_sizes(sq, sk, causal, window))
    bq, bk = min(bq, sq), min(bk, sk)

    # delta_i = rowsum(dO * O): the FA2 precompute — one fused XLA reduce
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp = _pad_to(q, 2, bq)
    dop = _pad_to(do, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    sqp, skp = qp.shape[2], kp.shape[2]
    nq, nk = sqp // bq, skp // bk
    # per-row stats carried lane-replicated [B, H, Sqp, _LANE] (tiling rule)
    lsep = jnp.broadcast_to(_pad_to(lse, 2, bq)[..., None],
                            (b, hq, sqp, _LANE))
    dltp = jnp.broadcast_to(_pad_to(delta, 2, bq)[..., None],
                            (b, hq, sqp, _LANE))

    # ONE fused kernel; grid (b, hq, k-blocks, q-blocks). dk/dv come out
    # per q head (B*Hq programs write disjoint slices) and are summed
    # over the GQA group afterwards.
    geom = dict(sq=sq, sk=sk, bq=bq, bk=bk)
    # the q blocks the grid walks for each k block: all of them, or with
    # a window those of the k block's band
    steps = nq if window is None else _bwd_q_steps(window=window, **geom)
    kernel = functools.partial(_bwd_fused_kernel, scale=scale,
                               causal=causal, has_seg=has_seg, **geom,
                               nq=nq, nk=nk, window=window, steps=steps)

    def kv_spec(width):
        return pl.BlockSpec(
            (1, 1, bk, width),
            lambda ib, ih, ikb, iqb, _rep=rep: (ib, ih // _rep, ikb, 0))

    def row_spec(width):
        return pl.BlockSpec((1, 1, sqp, width),
                            lambda ib, ih, ikb, iqb: (ib, ih, 0, 0))

    def kv_out_spec(width):
        return pl.BlockSpec((1, 1, bk, width),
                            lambda ib, ih, ikb, iqb: (ib, ih, ikb, 0))

    in_specs = [row_spec(d), kv_spec(d), kv_spec(dv), row_spec(dv),
                row_spec(_LANE), row_spec(_LANE)]
    args = [qp, kp, vp, dop, lsep, dltp]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, sqp, 1),
                         lambda ib, ih, ikb, iqb: (ib, 0, 0)),
            pl.BlockSpec((1, 1, skp),
                         lambda ib, ih, ikb, iqb: (ib, 0, 0)),
        ]
        args += [_pad_to(seg_q.astype(jnp.int32), 1, bq)[:, :, None],
                 _pad_to(seg_k.astype(jnp.int32), 1, bk)[:, None, :]]
    limit = _bwd_vmem_limit(sqp, d, q.dtype.itemsize, bq, bk, dv)
    plain, masked = (
        np.broadcast_to(kind, (nk, nq)).sum() for kind in _bwd_tile_kinds(
            np.arange(nk)[:, None], np.arange(nq)[None, :], causal=causal,
            has_seg=has_seg, window=window, **geom))
    _publish_tiles("bwd", q.shape, dv, sk, causal, has_seg, (bq, bk),
                   window, plain=plain, masked=masked,
                   skipped=nk * nq - plain - masked)
    # dq leaves in q's dtype; dk and dv too unless a GQA group is summed
    # outside, which wants them float32 until that sum
    kv_dtype = k.dtype if rep == 1 else jnp.float32
    dqh, dkh, dvh = pl.pallas_call(
        kernel,
        grid=(b, hq, nk, steps),
        in_specs=in_specs,
        out_specs=[row_spec(d), kv_out_spec(d), kv_out_spec(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sqp, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, skp, d), kv_dtype),
            jax.ShapeDtypeStruct((b, hq, skp, dv), kv_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((sqp, d), jnp.float32),   # dq rows (persistent)
            pltpu.VMEM((d, bk), jnp.float32),    # dk accumulator, transposed
            pltpu.VMEM((dv, bk), jnp.float32),   # dv accumulator, transposed
        ],
        interpret=interpret,
        name="flash_attention_bwd" if window is None else "flash_window_bwd",
        **({} if limit is None else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=limit)}),
    )(*args)
    if rep > 1:
        dkh = dkh.reshape(b, hk, rep, skp, d).sum(axis=2)
        dvh = dvh.reshape(b, hk, rep, skp, dv).sum(axis=2)
    return (dqh[:, :, :sq], dkh[:, :, :sk].astype(k.dtype),
            dvh[:, :, :sk].astype(v.dtype), None, None)


def flash_attention_bwd_jnp(q, k, v, do, o, lse, scale=None, causal=False,
                            segment_ids=None, blocks=None, window=None):
    """UNJITTED jnp twin of the fused Pallas backward (the
    ``quant_matmul_jnp`` parity contract).

    Takes paddle-layout [batch, seq, heads, head_dim] ``q/k/v/do``
    (``v``, ``do`` and ``o`` as wide as ``v``) plus
    the forward's ``o`` and logsumexp ``lse`` ([B, H, Sq], the second
    output of ``_fwd``), and replays the fused kernel's EXACT tile walk
    — the same padding, the same per-tile dot shapes and dimension
    numbers, the same accumulate order (k-blocks outer, q-blocks inner),
    the same masks and casts — so interpret-mode kernel grads are
    BITWISE equal on CPU for every geometry. Deliberately unjitted:
    jitted chains FMA-contract and drift the last ulp.

    Returns ``(dq, dk, dv)`` in paddle layout.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scale = float(scale)
    window = _window(window, causal, k.shape[1])
    seg_q = seg_k = None
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            seg_q, seg_k = segment_ids
        else:
            seg_q = seg_k = segment_ids
        seg_q = jnp.asarray(seg_q, jnp.int32)
        seg_k = jnp.asarray(seg_k, jnp.int32)
    q = jnp.swapaxes(q, 1, 2)   # -> [B, H, S, D]
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    do = jnp.swapaxes(do, 1, 2)
    o = jnp.swapaxes(o, 1, 2)
    b, hq, sq, d = q.shape
    hk, sk, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = hq // hk
    has_seg = seg_q is not None
    bq, bk = (blocks if blocks is not None
              else _bwd_block_sizes(sq, sk, causal, window))
    bq, bk = min(bq, sq), min(bk, sk)
    dt = q.dtype

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    qp = _pad_to(q, 2, bq)
    dop = _pad_to(do, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    sqp, skp = qp.shape[2], kp.shape[2]
    nq, nk = sqp // bq, skp // bk
    lsep = jnp.broadcast_to(_pad_to(lse, 2, bq)[..., None],
                            (b, hq, sqp, _LANE))
    dltp = jnp.broadcast_to(_pad_to(delta, 2, bq)[..., None],
                            (b, hq, sqp, _LANE))
    if has_seg:
        qsp = _pad_to(seg_q, 1, bq)
        ksp = _pad_to(seg_k, 1, bk)
    offset = sk - sq

    kv_dtype = k.dtype if rep == 1 else jnp.float32
    dqh = jnp.zeros((b, hq, sqp, d), q.dtype)
    dkh = jnp.zeros((b, hq, skp, d), kv_dtype)
    dvh = jnp.zeros((b, hq, skp, dv), kv_dtype)
    for ib in range(b):
        for ih in range(hq):
            dq_acc = jnp.zeros((sqp, d), jnp.float32)
            for ik in range(nk):
                kb = kp[ib, ih // rep, ik * bk:(ik + 1) * bk]
                vb = vp[ib, ih // rep, ik * bk:(ik + 1) * bk]
                dk_acc = jnp.zeros((d, bk), jnp.float32)
                dv_acc = jnp.zeros((dv, bk), jnp.float32)
                for iq in range(nq):
                    plain, masked = _bwd_tile_kinds(
                        np.asarray(ik), np.asarray(iq), causal=causal,
                        has_seg=has_seg, sq=sq, sk=sk, bq=bq, bk=bk,
                        window=window)
                    if not (plain or masked):
                        continue
                    qb = qp[ib, ih, iq * bq:(iq + 1) * bq]
                    dob = dop[ib, ih, iq * bq:(iq + 1) * bq]
                    lse_t = lsep[ib, ih, iq * bq:(iq + 1) * bq, 0:1]
                    dlt_t = dltp[ib, ih, iq * bq:(iq + 1) * bq, 0:1]
                    s = jax.lax.dot_general(
                        _scaled(qb, scale), kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    p = jnp.exp(s - lse_t)
                    if masked:
                        rows = (jax.lax.broadcasted_iota(
                            jnp.int32, (bq, bk), 0) + iq * bq)
                        cols = (jax.lax.broadcasted_iota(
                            jnp.int32, (bq, bk), 1) + ik * bk)
                        mask = (cols < sk) & (rows < sq)
                        if causal:
                            mask = mask & (rows + offset >= cols)
                        if window is not None:
                            mask = mask & (rows + offset - cols < window)
                        if has_seg:
                            qs = qsp[ib, iq * bq:(iq + 1) * bq]
                            ks = ksp[ib, ik * bk:(ik + 1) * bk]
                            mask = mask & (qs[:, None] == ks[None, :])
                        p = jnp.where(mask, p, 0.0)
                    dv_acc = dv_acc + jax.lax.dot_general(
                        dob, p.astype(dt), (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(
                        dob, vb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    ds = (p * (dp - dlt_t)).astype(dt)
                    dk_acc = dk_acc + jax.lax.dot_general(
                        qb, ds, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dq_acc = dq_acc.at[iq * bq:(iq + 1) * bq].set(
                        dq_acc[iq * bq:(iq + 1) * bq]
                        + jax.lax.dot_general(
                            ds, kb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
                dkh = dkh.at[ib, ih, ik * bk:(ik + 1) * bk].set(
                    (dk_acc.T * scale).astype(kv_dtype))
                dvh = dvh.at[ib, ih, ik * bk:(ik + 1) * bk].set(
                    dv_acc.T.astype(kv_dtype))
            dqh = dqh.at[ib, ih].set((dq_acc * scale).astype(q.dtype))
    if rep > 1:
        dkh = dkh.reshape(b, hk, rep, skp, d).sum(axis=2)
        dvh = dvh.reshape(b, hk, rep, skp, dv).sum(axis=2)
    return (jnp.swapaxes(dqh[:, :, :sq], 1, 2),
            jnp.swapaxes(dkh[:, :, :sk].astype(k.dtype), 1, 2),
            jnp.swapaxes(dvh[:, :, :sk].astype(v.dtype), 1, 2))


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
def _window(window, causal, sk):
    """A call's window as the kernels take it: None where there is none
    or where it is no shorter than the keys (every key a causal row can
    see is then within it: the plain causal programs)."""
    if window is None:
        return None
    if not causal:
        raise ValueError("flash_attention: a window is a lower edge under "
                         "the causal diagonal; it needs causal=True")
    if window < 1:
        raise ValueError(f"flash_attention: a window of {window} keys")
    return None if window >= sk else int(window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_bhsd(q, k, v, seg_q, seg_k, scale, causal, interpret,
                blocks=None, bwd_blocks=None, window=None):
    o, _ = _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks,
                window)
    return o


def _flash_fwd_rule(q, k, v, seg_q, seg_k, scale, causal, interpret,
                    blocks=None, bwd_blocks=None, window=None):
    o, lse = _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks,
                  window)
    return o, (q, k, v, seg_q, seg_k, o, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


_TUNE_CANDIDATES = ((128, 128), (256, 256), (256, 512), (512, 256),
                    (512, 512), (512, 1024), (1024, 512), (1024, 1024))
# backward candidates: the fused backward kernel carries full-row
# q/do/dq VMEM buffers plus per-k-block dk/dv accumulators and asks for
# its own VMEM limit by block pair (``_bwd_vmem_limit``), so the sweep
# reaches the measured default, one 1024 x 1024 tile; beyond it the
# tile's temporaries near the v5e's 128 MiB and lost on the chip.
_TUNE_BWD_CANDIDATES = ((128, 128), (128, 256), (256, 128), (256, 256),
                        (256, 512), (512, 256), (512, 512), (512, 1024),
                        (1024, 512), (1024, 1024))


def _scan_slope(make_runner, args, r1=4, r2=24):
    """Dispatch-free kernel timing: ``reps`` applications scanned inside
    ONE jit (the q input is index-perturbed so XLA cannot CSE the
    iterations; the scan compiles each kernel once regardless of reps).
    The difference between two rep counts is pure kernel time — constant
    dispatch latency cancels; per-call wall timing is dominated by the
    host's dispatch jitter and picks wrong winners.
    Returns seconds/rep, or inf when below timing resolution (noise must
    never crown a winner)."""
    def _timed(reps):
        f = make_runner(reps)
        out = f(*args)
        float(jax.device_get(out.ravel()[0]))  # compile/warm + sync
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = f(*args)
            float(jax.device_get(out.ravel()[0]))
            best = min(best, time.perf_counter() - t0)
        return best

    slope = (_timed(r2) - _timed(r1)) / (r2 - r1)
    return slope if slope > 0 else float("inf")


def _tuned_entry(entry, candidates, qt, kt, vt, causal, make_runner,
                 validate, window=None):
    """Shared cache-probe / sweep / fallback protocol for both flash
    autotune entries. Under a trace (tracer inputs) only cache HITS
    apply — the shapes are static so the key is known; the measuring
    sweep runs when inputs are concrete (first eager call, or an
    explicit warmup call). On a sweep where every candidate
    failed or timed below resolution, fall back to the measured
    defaults rather than crashing the call (nothing is cached, so a
    later quieter run can still tune)."""
    from . import autotune as at
    sq, sk = qt.shape[2], kt.shape[2]
    cands = [c for c in candidates if c[0] <= sq and c[1] <= sk]
    if len(cands) <= 1:
        return None
    sig = _shape_sig(qt.shape, sk, causal, vt.shape[-1], window)
    cached = at._load_cache().get(f"{at._device_kind()}|{entry}|{sig}")
    if cached is not None:
        for c in cands:
            if at._same_candidate(c, cached):
                return tuple(c)
    if isinstance(qt, jax.core.Tracer):
        return None  # no timing possible mid-trace; use defaults
    runners = {}

    def memo_runner(cand, reps):
        f = runners.get((cand, reps))
        if f is None:
            f = runners[(cand, reps)] = jax.jit(make_runner(cand, reps))
        return f

    def measure(cand):
        return _scan_slope(lambda reps: memo_runner(cand, reps),
                           (qt, kt, vt))

    try:
        return tuple(at.autotune(entry, sig, cands, None,
                                 measure=measure, validate=validate))
    except RuntimeError:
        return None


def _autotuned_blocks(qt, kt, scale, causal, vt=None, window=None):
    """FORWARD block-size selection through the autotune cache (SURVEY
    C14; see autotune.py). The backward tunes separately
    (``_autotuned_bwd_blocks``) — its fused kernel has different VMEM
    pressure and different winners, and fwd+bwd-blended timing used to
    bias both."""
    vt = kt if vt is None else vt       # values as wide as the keys

    def make_runner(cand, reps):
        def chained(a, bb, cc, _n=reps, _cand=tuple(cand)):
            def body(c, i):
                o = _flash_bhsd(a + i.astype(a.dtype) * 1e-6, bb, cc,
                                None, None, scale, causal, False,
                                _cand, None, window)
                return c + o.astype(a.dtype), None
            z = jnp.zeros((*a.shape[:-1], cc.shape[-1]), a.dtype)
            return jax.lax.scan(body, z, jnp.arange(_n))[0]
        return chained

    def validate(cand):
        # the measuring jit may fuse/lay out differently than the real
        # call: compile+run the forward in the caller's real eager
        # context — a scoped-vmem overflow disqualifies the candidate
        # and the next-best wins.
        o = _flash_bhsd(qt, kt, vt, None, None, scale, causal, False,
                        tuple(cand), None, window)
        float(jax.device_get(o.ravel()[0]))  # force execution

    return _tuned_entry("flash_attention", _TUNE_CANDIDATES, qt, kt, vt,
                        causal, make_runner, validate, window)


def _autotuned_bwd_blocks(qt, kt, scale, causal, fwd_blocks, vt=None,
                          window=None):
    """BACKWARD block-size selection: its own ``flash_attention_bwd``
    autotune entry over backward-specific candidates
    (``_TUNE_BWD_CANDIDATES``). The timed program is the full fwd+bwd chain
    with the FORWARD blocks pinned to the already-tuned winner: the
    forward term is constant across candidates, so the slope ranks the
    backward kernels alone."""
    vt = kt if vt is None else vt

    def make_runner(cand, reps):
        grad = jax.grad(
            lambda a, bb, cc, _cand=tuple(cand): _flash_bhsd(
                a, bb, cc, None, None, scale, causal, False,
                fwd_blocks, _cand, window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))

        def chained(a, bb, cc, _n=reps):
            def body(c, i):
                # every grad output must feed the carry: an unused
                # dk/dv would let XLA dead-code-eliminate their
                # accumulation from the timed program. dk/dv fold in
                # as scalars so rectangular attention (sq != sk)
                # stays timeable.
                dq, dk, dv = grad(a + i.astype(a.dtype) * 1e-6, bb, cc)
                extra = (dk.sum() + dv.sum()).astype(a.dtype)
                return c + dq.astype(a.dtype) + extra, None
            z = jnp.zeros(a.shape, a.dtype)
            return jax.lax.scan(body, z, jnp.arange(_n))[0]
        return chained

    def validate(cand):
        # the fused backward has the larger vmem footprint (full-row
        # q/do/dq buffers + the dk/dv accumulators). Compile+run fwd AND
        # bwd in the caller's real eager context — a scoped-vmem
        # overflow disqualifies the candidate and the next-best wins.
        def f(a, bb, cc):
            return _flash_bhsd(
                a, bb, cc, None, None, scale, causal, False, fwd_blocks,
                tuple(cand), window).astype(jnp.float32).sum()
        grads = jax.grad(f, argnums=(0, 1, 2))(qt, kt, vt)
        float(jax.device_get(grads[0].ravel()[0]))  # force execution

    return _tuned_entry("flash_attention_bwd", _TUNE_BWD_CANDIDATES,
                        qt, kt, vt, causal, make_runner, validate, window)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None,
                    blocks=None, segment_ids=None, bwd_blocks=None,
                    window=None):
    """Flash attention in paddle layout [batch, seq, num_heads, head_dim].

    ``num_heads(q)`` may be a multiple of ``num_heads(k) == num_heads(v)``
    (grouped-query attention).  ``head_dim(k)`` must equal ``head_dim(q)``
    (the scores contract over it; the default ``scale`` is
    ``1 / sqrt(head_dim(q))``); ``head_dim(v)`` is its own, and the
    result is [batch, seq_q, num_heads, head_dim(v)]: ``do`` and ``dv``
    are as wide as ``v``, ``dq`` and ``dk`` as wide as ``q``.  A
    ``ValueError`` says which of these a call breaks.
    ``blocks``: optional (block_q, block_k) override; with autotuning
    enabled (``incubate.autotune.set_config``) the best pair is measured
    on-device and cached per shape. ``bwd_blocks``: the same for the
    fused backward kernel (its own ``flash_attention_bwd`` autotune
    entry — backward winners differ from forward ones).
    ``segment_ids``: varlen/packed-sequence support (the capability of the
    reference's ``flash_attn_varlen_fwd``,
    ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:91``): an int array
    [batch, seq] (shared q/kv when lengths match) or a pair
    ``(q_seg [B,Sq], kv_seg [B,Sk])``; attention is confined to positions
    with equal segment id, composing with ``causal``.
    ``window``: sliding-window attention (HF ``sliding_window``), with
    ``causal=True`` only: query ``i`` sees the ``window`` keys ``j`` with
    ``i - window < j <= i``, itself among them (positions counted from
    the END of the keys where ``seq_q != seq_k``, as the diagonal is).
    The kernels visit no tile wholly behind the window; a window no
    shorter than the keys is the plain causal call.
    """
    window = _window(window, causal, k.shape[1])
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    hq, hk = q.shape[2], k.shape[2]
    if hk == 0 or hq % hk != 0:
        raise ValueError(
            f"flash_attention: query heads ({hq}) must be a multiple of "
            f"key/value heads ({hk}) for grouped-query attention")
    if k.shape[-1] != q.shape[-1]:
        raise ValueError(
            f"flash_attention: k's head_dim ({k.shape[-1]}) must equal q's "
            f"({q.shape[-1]}): the scores contract over it; only v's "
            f"head_dim may differ")
    if v.shape[:-1] != k.shape[:-1]:
        raise ValueError(
            f"flash_attention: v {tuple(v.shape)} must match k "
            f"{tuple(k.shape)} in batch, positions and heads; its head_dim "
            f"is its own")
    seg_q = seg_k = None
    if segment_ids is not None:
        if isinstance(segment_ids, (tuple, list)):
            seg_q, seg_k = segment_ids
        else:
            if q.shape[1] != k.shape[1]:
                raise ValueError(
                    "flash_attention: a single segment_ids array needs "
                    "seq_q == seq_k; pass (q_seg, kv_seg) otherwise")
            seg_q = seg_k = segment_ids
        seg_q = jnp.asarray(seg_q, jnp.int32)
        seg_k = jnp.asarray(seg_k, jnp.int32)
    qt = jnp.swapaxes(q, 1, 2)  # -> [B, H, S, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if not interpret and segment_ids is None:
        from . import autotune as at
        if at.enabled():
            # a caller-pinned blocks= opts OUT of tuning entirely (the
            # pre-split behavior; the pinned pair also drives the
            # backward through _bwd's fallback chain)
            if blocks is None:
                blocks = _autotuned_blocks(qt, kt, float(scale),
                                           bool(causal), vt, window)
                if bwd_blocks is None:
                    bwd_blocks = _autotuned_bwd_blocks(
                        qt, kt, float(scale), bool(causal), blocks, vt,
                        window)
    o = _flash_bhsd(qt, kt, vt, seg_q, seg_k, float(scale), bool(causal),
                    bool(interpret), blocks, bwd_blocks, window)
    return jnp.swapaxes(o, 1, 2)
