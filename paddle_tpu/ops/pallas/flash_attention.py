"""Pallas TPU flash attention (forward + backward, causal + GQA).

Capability analog of the reference FlashAttention-2 integration
(``paddle/phi/kernels/gpu/flash_attn_kernel.cu:91`` fwd,
``flash_attn_grad_kernel.cu`` bwd, python surface
``python/paddle/nn/functional/flash_attention.py:147``) — TPU-native design:

* online-softmax tiling sized for the MXU (q blocks x k blocks, fp32
  accumulators in registers/VMEM, bf16 matmul inputs);
* per-(batch, head) grid programs keep K/V resident in VMEM while a q block
  streams through — no [S, S] score matrix ever exists in HBM;
* causal programs stop the k loop at the diagonal block (the FA2 trick that
  halves causal FLOPs);
* grouped-query attention maps q-head -> kv-head in the BlockSpec index map
  (no materialized ``repeat`` of K/V, unlike the XLA fallback);
* backward recomputes the softmax from the saved logsumexp (flash-attn
  recompute strategy) in ONE fused kernel: a 4-D grid walks (k-block,
  q-block) tiles, recomputing the attention probabilities ONCE per tile
  and producing dk/dv (VMEM accumulators over the q grid dim) AND dq (a
  persistent full-row VMEM scratch accumulated over the k grid dim) from
  the same ``p``/``ds`` — the previous two-pass backward paid the s/p
  recompute twice (7 tile dots; fused is 5, the ~2.5x-over-forward FLOP
  ideal instead of the measured 4.5x).

Parity discipline (the ``quant_matmul_jnp`` contract):
``flash_attention_bwd_jnp`` is an UNJITTED jnp twin replaying the fused
kernel's exact tile walk — same per-tile dot shapes, same accumulate
order, same masks — so Pallas-interpret backward grads are BITWISE equal
to the twin on CPU for every geometry (causal x GQA x segment-ids x
padded tails). Backward block sizes are tuned separately from the
forward under the ``flash_attention_bwd`` autotune entry (the backward's
VMEM footprint — full-row q/do/dq buffers plus the k-tile accumulators —
admits different winners than the forward).

Public entry: ``flash_attention(q, k, v, causal=..., scale=...)`` in
paddle's [batch, seq, num_heads, head_dim] layout, differentiable via
``jax.custom_vjp``.
"""
from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp() NaN-free
_LANE = 8  # trailing lane width for per-row stats (Mosaic tile alignment)


def _block_sizes(sq, sk):
    """Default (block_q, block_k). Measured on the v5e-class chip with the
    dispatch-free scan-slope method (benchmarks/attn_sweep.py): 512x512 is
    3-8x faster than 128x128 at b8/h12/s1024/d64 (fwd 0.41 ms vs 1.46 ms;
    grad call 0.36-1.2 ms vs 2.96 ms) — bigger q/k tiles amortize the
    per-block softmax/stat work over more MXU cycles. VMEM stays
    comfortable: K/V are already held full-length per (batch, head)
    program."""
    bq = min(512, sq)
    bk = min(512, sk)
    return bq, bk


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, has_seg,
                sq, sk, bq, bk):
    """One (batch, q-head, q-block) program: stream k/v blocks with online
    softmax. Block shapes: q/o [1,1,bq,D]; k/v [1,1,Skp,D]; lse
    [1,1,bq,LANE] (Mosaic needs the trailing dims tile-aligned, so the
    per-row logsumexp is replicated across a small lane axis). With
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
    q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, D]
    offset = sk - sq                                   # causal diagonal shift

    nk = pl.cdiv(sk, bk)
    if causal:
        # last k block that the last row of this q block can see
        hi = jnp.minimum(nk, ((iq + 1) * bq + offset + bk - 1) // bk)
    else:
        hi = nk

    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
    cols0 = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    def body(j, carry):
        m_i, l_i, acc = carry
        kb = k_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # [bq, bk]
        cols = cols0 + j * bk
        mask = cols < sk                               # k padding
        if causal:
            mask = mask & (rows + offset >= cols)
        if has_seg:
            qs = qs_ref[0]                             # [bq, 1]
            ks = ks_ref[0, :, pl.ds(j * bk, bk)]       # [1, bk]
            mask = mask & (qs == ks)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                         # [bq, bk]
        alpha = jnp.exp(m_i - m_new)                   # [bq, 1]
        l_new = l_i * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)
    m_f, l_f, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, a0))

    l_safe = jnp.where(l_f == 0.0, 1.0, l_f)           # padded q rows
    o_ref[0, 0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = jnp.broadcast_to(m_f + jnp.log(l_safe), (bq, _LANE))


def _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks=None):
    """q [B,Hq,Sq,D]; k,v [B,Hk,Sk,D]; seg_q/seg_k optional [B,Sq]/[B,Sk]
    int32 segment ids -> (o [B,Hq,Sq,D], lse [B,Hq,Sq])."""
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    rep = hq // hk
    has_seg = seg_q is not None
    bq, bk = blocks if blocks is not None else _block_sizes(sq, sk)
    bq, bk = min(bq, sq), min(bk, sk)
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    sqp, skp = qp.shape[2], kp.shape[2]
    grid = (b, hq, sqp // bq)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               has_seg=has_seg, sq=sq, sk=sk, bq=bq, bk=bk)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, skp, d),
                     lambda ib, ih, iq, _rep=rep: (ib, ih // _rep, 0, 0)),
        pl.BlockSpec((1, 1, skp, d),
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
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, _LANE),
                         lambda ib, ih, iq: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sqp, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, sqp, _LANE), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)
    return o[:, :, :sq], lse[:, :, :sq, 0]


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_block_sizes(sq, sk):
    """Default backward (block_q, block_k). The fused kernel holds
    full-row q/do/dq buffers regardless of the block pair, so the tile
    choice trades MXU utilization against the dk/dv accumulator + k/v
    tile footprint only; 512x512 matches the measured forward default
    and is re-tuned per shape under the ``flash_attention_bwd`` autotune
    entry."""
    return min(512, sq), min(512, sk)


_SCOPED_VMEM = 16 << 20     # what Mosaic gives a kernel unless told
_TILE_VMEM = 8 << 20        # room for the k/v tiles, p, ds and dp


def _bwd_vmem_limit(sqp, d, itemsize):
    """``vmem_limit_bytes`` for the fused backward, or None where the
    default holds it.  The kernel keeps the whole row of q, do, the
    lane-replicated lse and delta (double-buffered inputs), dq (a
    double-buffered output) and the dq accumulator resident, each
    padded to 128 lanes: 4.5 KB a position at head_dim 64 in bfloat16,
    so 4.7 MB at 1024 positions and 37.7 MB at 8192, which the chip's
    compiler refuses under the 16 MB default (the v5e has 128 MiB)."""
    def lanes(n):
        return -(-n // 128) * 128
    rows = sqp * (2 * 2 * lanes(d) * itemsize + 2 * 2 * lanes(_LANE) * 4
                  + 2 * lanes(d) * 4 + lanes(d) * 4)
    need = rows + _TILE_VMEM
    return None if need <= _SCOPED_VMEM else need


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *refs, scale, causal, has_seg, sq, sk, bq, bk,
                      nq, nk):
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
    never-attending rows still produce zeros.
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

    @pl.when(iq == 0)
    def _zero_kv_acc():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    @pl.when(ik == 0)
    def _zero_dq_slice():
        dq_acc[pl.ds(iq * bq, bq), :] = jnp.zeros(
            (bq, dq_acc.shape[-1]), jnp.float32)

    if causal:
        lo = jnp.maximum(0, (ik * bk - offset) // bq)  # first attending q
        active = iq >= lo
    else:
        active = None

    def tile():
        kb = k_ref[0, 0].astype(jnp.float32)           # [bk, D]
        vb = v_ref[0, 0].astype(jnp.float32)
        qb = q_ref[0, 0, pl.ds(iq * bq, bq), :].astype(jnp.float32) * scale
        dob = do_ref[0, 0, pl.ds(iq * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(iq * bq, bq), 0:1]   # [bq, 1]
        dlt = delta_ref[0, 0, pl.ds(iq * bq, bq), 0:1]
        s = jax.lax.dot_general(
            qb, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
        mask = (cols < sk) & (rows < sq)
        if causal:
            mask = mask & (rows + offset >= cols)
        if has_seg:
            qs = qs_ref[0, pl.ds(iq * bq, bq), :]      # [bq, 1]
            ks = ks_ref[0, :, pl.ds(ik * bk, bk)]      # [1, bk]
            mask = mask & (qs == ks)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)     # recomputed ONCE
        dv_acc[...] += jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, D]
        dp = jax.lax.dot_general(
            dob, vb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bq, bk]
        ds = p * (dp - dlt)                            # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [bk, D]
        # accumulate UNSCALED: a fused multiply in the accumulate chain
        # FMA-contracts under compilation and drifts the last ulp vs the
        # unjitted twin; the single scale multiply happens at flush
        dq_acc[pl.ds(iq * bq, bq), :] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(active)(tile)
    else:
        tile()

    # flush dq once this q row's LAST attending k block has run. hi can
    # be <= 0 for rows that attend nothing (sq > sk rectangles): clamp
    # to 1 so the zeroed slice still flushes at ik == 0.
    if causal:
        hi = jnp.minimum(nk, ((iq + 1) * bq + offset + bk - 1) // bk)
        hi = jnp.maximum(hi, 1)
    else:
        hi = nk

    @pl.when(ik == hi - 1)
    def _flush_dq():
        dq_ref[0, 0, pl.ds(iq * bq, bq), :] = \
            (dq_acc[pl.ds(iq * bq, bq), :] * scale).astype(dq_ref.dtype)

    @pl.when(iq == nq - 1)
    def _flush_kv():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(scale, causal, interpret, blocks, bwd_blocks, res, g):
    q, k, v, seg_q, seg_k, o, lse = res
    do = g
    b, hq, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    rep = hq // hk
    has_seg = seg_q is not None
    # precedence: explicit bwd_blocks > the forward's (possibly caller-
    # pinned) pair > the measured default — a caller who pinned blocks=
    # gets the pre-split behavior of one pair driving both directions
    bq, bk = (bwd_blocks if bwd_blocks is not None
              else blocks if blocks is not None
              else _bwd_block_sizes(sq, sk))
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
    kernel = functools.partial(_bwd_fused_kernel, scale=scale,
                               causal=causal, has_seg=has_seg, sq=sq,
                               sk=sk, bq=bq, bk=bk, nq=nq, nk=nk)
    kv_spec = pl.BlockSpec(
        (1, 1, bk, d),
        lambda ib, ih, ikb, iqb, _rep=rep: (ib, ih // _rep, ikb, 0))
    q_full = pl.BlockSpec((1, 1, sqp, d),
                          lambda ib, ih, ikb, iqb: (ib, ih, 0, 0))
    v1_full = pl.BlockSpec((1, 1, sqp, _LANE),
                           lambda ib, ih, ikb, iqb: (ib, ih, 0, 0))
    in_specs = [q_full, kv_spec, kv_spec, q_full, v1_full, v1_full]
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
    limit = _bwd_vmem_limit(sqp, d, q.dtype.itemsize)
    dqh, dkh, dvh = pl.pallas_call(
        kernel,
        grid=(b, hq, nk, nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, sqp, d),
                         lambda ib, ih, ikb, iqb: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ikb, iqb: (ib, ih, ikb, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda ib, ih, ikb, iqb: (ib, ih, ikb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, sqp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, skp, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, skp, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((sqp, d), jnp.float32),   # dq rows (persistent)
            pltpu.VMEM((bk, d), jnp.float32),    # dk accumulator
            pltpu.VMEM((bk, d), jnp.float32),    # dv accumulator
        ],
        interpret=interpret,
        name="flash_attention_bwd",
        **({} if limit is None else {
            "compiler_params": pltpu.CompilerParams(
                vmem_limit_bytes=limit)}),
    )(*args)
    if rep > 1:
        dkh = dkh.reshape(b, hk, rep, skp, d).sum(axis=2)
        dvh = dvh.reshape(b, hk, rep, skp, d).sum(axis=2)
    dk = dkh[:, :, :sk].astype(k.dtype)
    dv = dvh[:, :, :sk].astype(v.dtype)
    dq = dqh[:, :, :sq].astype(q.dtype)
    return dq, dk, dv, None, None


def flash_attention_bwd_jnp(q, k, v, do, o, lse, scale=None, causal=False,
                            segment_ids=None, blocks=None):
    """UNJITTED jnp twin of the fused Pallas backward (the
    ``quant_matmul_jnp`` parity contract).

    Takes paddle-layout [batch, seq, heads, head_dim] ``q/k/v/do`` plus
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
    hk, sk = k.shape[1], k.shape[2]
    rep = hq // hk
    has_seg = seg_q is not None
    bq, bk = blocks if blocks is not None else _bwd_block_sizes(sq, sk)
    bq, bk = min(bq, sq), min(bk, sk)

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

    dqh = jnp.zeros((b, hq, sqp, d), jnp.float32)
    dkh = jnp.zeros((b, hq, skp, d), jnp.float32)
    dvh = jnp.zeros((b, hq, skp, d), jnp.float32)
    for ib in range(b):
        for ih in range(hq):
            dq_acc = jnp.zeros((sqp, d), jnp.float32)
            for ik in range(nk):
                kb = kp[ib, ih // rep,
                        ik * bk:(ik + 1) * bk].astype(jnp.float32)
                vb = vp[ib, ih // rep,
                        ik * bk:(ik + 1) * bk].astype(jnp.float32)
                dk_acc = jnp.zeros((bk, d), jnp.float32)
                dv_acc = jnp.zeros((bk, d), jnp.float32)
                lo = max(0, (ik * bk - offset) // bq) if causal else 0
                for iq in range(nq):
                    if iq < lo:
                        continue
                    qb = qp[ib, ih, iq * bq:(iq + 1) * bq] \
                        .astype(jnp.float32) * scale
                    dob = dop[ib, ih, iq * bq:(iq + 1) * bq] \
                        .astype(jnp.float32)
                    lse_t = lsep[ib, ih, iq * bq:(iq + 1) * bq, 0:1]
                    dlt_t = dltp[ib, ih, iq * bq:(iq + 1) * bq, 0:1]
                    s = jax.lax.dot_general(
                        qb, kb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    rows = (jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 0) + iq * bq)
                    cols = (jax.lax.broadcasted_iota(
                        jnp.int32, (bq, bk), 1) + ik * bk)
                    mask = (cols < sk) & (rows < sq)
                    if causal:
                        mask = mask & (rows + offset >= cols)
                    if has_seg:
                        qs = qsp[ib, iq * bq:(iq + 1) * bq]
                        ks = ksp[ib, ik * bk:(ik + 1) * bk]
                        mask = mask & (qs[:, None] == ks[None, :])
                    p = jnp.where(mask, jnp.exp(s - lse_t), 0.0)
                    dv_acc = dv_acc + jax.lax.dot_general(
                        p, dob, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(
                        dob, vb, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    ds = p * (dp - dlt_t)
                    dk_acc = dk_acc + jax.lax.dot_general(
                        ds, qb, (((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dq_acc = dq_acc.at[iq * bq:(iq + 1) * bq].set(
                        dq_acc[iq * bq:(iq + 1) * bq]
                        + jax.lax.dot_general(
                            ds, kb, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
                dkh = dkh.at[ib, ih, ik * bk:(ik + 1) * bk].set(dk_acc)
                dvh = dvh.at[ib, ih, ik * bk:(ik + 1) * bk].set(dv_acc)
            dqh = dqh.at[ib, ih].set(dq_acc * scale)
    if rep > 1:
        dkh = dkh.reshape(b, hk, rep, skp, d).sum(axis=2)
        dvh = dvh.reshape(b, hk, rep, skp, d).sum(axis=2)
    dk = dkh[:, :, :sk].astype(k.dtype)
    dv = dvh[:, :, :sk].astype(v.dtype)
    dq = dqh[:, :, :sq].astype(q.dtype)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, seg_q, seg_k, scale, causal, interpret,
                blocks=None, bwd_blocks=None):
    o, _ = _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks)
    return o


def _flash_fwd_rule(q, k, v, seg_q, seg_k, scale, causal, interpret,
                    blocks=None, bwd_blocks=None):
    o, lse = _fwd(q, k, v, seg_q, seg_k, scale, causal, interpret, blocks)
    return o, (q, k, v, seg_q, seg_k, o, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


_TUNE_CANDIDATES = ((128, 128), (256, 256), (256, 512), (512, 256),
                    (512, 512), (512, 1024), (1024, 512), (1024, 1024))
# backward candidates: the fused backward kernel carries full-row
# q/do/dq VMEM buffers plus per-k-block dk/dv accumulators — a larger
# fixed footprint than the forward (the old shared-candidate scheme let
# the backward inherit forward-biased winners; see the validate() note
# below) — so the sweep stays at or below 512x512 tiles where the
# accumulators plus the k/v tiles cannot tip a full-row budget over.
_TUNE_BWD_CANDIDATES = ((128, 128), (128, 256), (256, 128), (256, 256),
                        (256, 512), (512, 256), (512, 512))


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


def _tuned_entry(entry, candidates, qt, kt, causal, make_runner,
                 validate):
    """Shared cache-probe / sweep / fallback protocol for both flash
    autotune entries. Under a trace (tracer inputs) only cache HITS
    apply — the shapes are static so the key is known; the measuring
    sweep runs when inputs are concrete (first eager call, or an
    explicit warmup like bench.py's). On a sweep where every candidate
    failed or timed below resolution, fall back to the measured
    defaults rather than crashing the call (nothing is cached, so a
    later quieter run can still tune)."""
    from . import autotune as at
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    cands = [c for c in candidates if c[0] <= sq and c[1] <= sk]
    if len(cands) <= 1:
        return None
    sig = f"b{b}h{h}sq{sq}sk{sk}d{d}c{int(causal)}"
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
                           (qt, kt, kt))

    try:
        return tuple(at.autotune(entry, sig, cands, None,
                                 measure=measure, validate=validate))
    except RuntimeError:
        return None


def _autotuned_blocks(qt, kt, scale, causal):
    """FORWARD block-size selection through the autotune cache (SURVEY
    C14; see autotune.py). The backward tunes separately
    (``_autotuned_bwd_blocks``) — its fused kernel has different VMEM
    pressure and different winners, and fwd+bwd-blended timing used to
    bias both."""

    def make_runner(cand, reps):
        def chained(a, bb, cc, _n=reps, _cand=tuple(cand)):
            def body(c, i):
                o = _flash_bhsd(a + i.astype(a.dtype) * 1e-6, bb, cc,
                                None, None, scale, causal, False,
                                _cand)
                return c + o.astype(a.dtype), None
            z = jnp.zeros(a.shape, a.dtype)
            return jax.lax.scan(body, z, jnp.arange(_n))[0]
        return chained

    def validate(cand):
        # the measuring jit may fuse/lay out differently than the real
        # call: compile+run the forward in the caller's real eager
        # context — a scoped-vmem overflow disqualifies the candidate
        # and the next-best wins.
        o = _flash_bhsd(qt, kt, kt, None, None, scale, causal, False,
                        tuple(cand))
        float(jax.device_get(o.ravel()[0]))  # force execution

    return _tuned_entry("flash_attention", _TUNE_CANDIDATES, qt, kt,
                        causal, make_runner, validate)


def _autotuned_bwd_blocks(qt, kt, scale, causal, fwd_blocks):
    """BACKWARD block-size selection: its own ``flash_attention_bwd``
    autotune entry over backward-specific candidates
    (``_TUNE_BWD_CANDIDATES`` — the fused kernel's VMEM footprint is
    larger than the forward's, so forward-biased 1024-tile candidates
    are excluded up front). The timed program is the full fwd+bwd chain
    with the FORWARD blocks pinned to the already-tuned winner: the
    forward term is constant across candidates, so the slope ranks the
    backward kernels alone."""

    def make_runner(cand, reps):
        grad = jax.grad(
            lambda a, bb, cc, _cand=tuple(cand): _flash_bhsd(
                a, bb, cc, None, None, scale, causal, False,
                fwd_blocks, _cand).astype(jnp.float32).sum(),
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
                tuple(cand)).astype(jnp.float32).sum()
        grads = jax.grad(f, argnums=(0, 1, 2))(qt, kt, kt)
        float(jax.device_get(grads[0].ravel()[0]))  # force execution

    return _tuned_entry("flash_attention_bwd", _TUNE_BWD_CANDIDATES,
                        qt, kt, causal, make_runner, validate)


def flash_attention(q, k, v, causal=False, scale=None, interpret=None,
                    blocks=None, segment_ids=None, bwd_blocks=None):
    """Flash attention in paddle layout [batch, seq, num_heads, head_dim].

    ``num_heads(q)`` may be a multiple of ``num_heads(k) == num_heads(v)``
    (grouped-query attention). Returns [batch, seq_q, num_heads, head_dim].
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
    """
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
                                           bool(causal))
                if bwd_blocks is None:
                    bwd_blocks = _autotuned_bwd_blocks(
                        qt, kt, float(scale), bool(causal), blocks)
    o = _flash_bhsd(qt, kt, vt, seg_q, seg_k, float(scale), bool(causal),
                    bool(interpret), blocks, bwd_blocks)
    return jnp.swapaxes(o, 1, 2)
