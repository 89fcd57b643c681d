"""Pallas TPU ragged paged-KV attention (decode + mixed prefill/decode).

Capability analog of the reference's paged/block KV serving kernels
(``paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu``,
``masked_multihead_attention_kernel.cu``) in the TPU-native shape of
"Ragged Paged Attention" (arxiv 2604.15464 / PAPERS.md):

* the KV cache lives in a PAGE POOL ``[num_kv_heads, total_pages,
  page_size, head_dim]``; each sequence owns a list of page indices (its
  block table) instead of a contiguous ``max_len`` slab, so HBM scales
  with tokens actually generated;
* the grid is COMPACTED through the scalar-prefetch channel: the host
  (or the enclosing jit) computes cumulative per-sequence kv-block
  counts and flattens the real (sequence, q-block, kv-block) work items
  onto one grid axis — programs exist only for blocks inside each
  sequence's true length (plus a static-budget tail that exits
  immediately).  The pools stay in HBM: every page slot of a work
  item is one ``(page_size, head_dim)`` BlockSpec window whose page id
  comes from the scalar-prefetched plan, so the pipeline fetches only
  the pages a live item names (the budget tail repeats the last live
  item's pages, and a repeated window moves no bytes).  A whole-page
  window spans the pool's full minor dims, which Mosaic accepts at any
  head_dim — a hand-issued DMA of the same page is refused below 128
  lanes ("slice shape ... must be aligned to tiling (128)");
* each program walks ``pages_per_block`` pages, amortizing the
  sublane-padded q block across ``pages_per_block * page_size`` KV
  tokens per grid step (the one-page-per-program version re-fetched the
  q block once per page). ``pages_per_block`` is an autotunable free
  parameter (``ops/pallas/autotune.py``);
* RAGGED batches: ``ragged_paged_attention`` takes packed q tokens with
  per-sequence ``q_lens`` — decode rows (q_len 1) and prefill rows
  (q_len = prompt chunk) share ONE kernel call, the shape a
  continuous-batching step needs (``paddle_tpu/inference/engine.py``).
  Causality is positional: q token ``i`` of a sequence attends kv
  positions ``<= kv_len - q_len + i``;
* online softmax across a sequence's kv blocks in VMEM scratch (same
  flash recurrence as flash_attention.py); GQA by grouping the
  ``rep = Hq // Hk`` query heads of a kv head into the sublane
  dimension.

* INT8 KV pages (ISSUE 7): when the pools are int8, per-page scale
  side-pools [Hk, P, page_size] (``quantization.kv_quantize``) are
  windowed alongside each data page and the dequant happens in VMEM
  on the fetched block — attention reads a QUARTER of the fp32 KV
  bytes per step, which is the serving roofline term, and no float
  page ever exists in HBM.

Public entries: ``paged_decode_attention`` (one token per sequence —
the ``models.generate(kv_cache='paged')`` path, API-compatible with the
previous kernel) and ``ragged_paged_attention`` (mixed token counts —
the serving engine path).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import out_struct

NEG_INF = -1e30
_LANE = 128    # lane width for per-row stats kept in VMEM scratch
_MIN_SUB = 8   # Mosaic sublane minimum for the q-block row dimension


def _cdiv(a, b):
    return (a + b - 1) // b


def _row_pad(q_block, rep):
    """Smallest ``rep_p >= rep`` with ``q_block * rep_p`` a sublane
    multiple — the kernel's q-block row count is ``q_block * rep_p``
    ((token, q-head-of-group) pairs stacked in the sublane dim)."""
    rep_p = rep
    while (q_block * rep_p) % _MIN_SUB:
        rep_p += 1
    return rep_p


# --------------------------------------------------------------------------
# work-item planning (grid compaction)
# --------------------------------------------------------------------------

def _plan_items(kv_lens, q_lens, block_tables, *, q_block, page_size,
                pages_per_block, num_pages, nqb_total, item_budget):
    """Flatten the ragged (sequence, q-block, kv-block) work triples onto
    one grid axis.  Pure jnp — runs on concrete arrays (eager call) and
    on tracers (inside a jitted serving step; the arrays ride the
    scalar-prefetch channel, so changing lengths never recompile).

    Returns int32 arrays sized by the STATIC budgets:
      seq[i], qb[i]   — owning sequence / q block within it
      kb[i]           — kv block within the sequence
      qbg[i]          — global q-block index (output/q BlockSpec target;
                        budget tail repeats the last live value so the
                        pipeline never flaps blocks)
      first[i]/last[i]— 1 on the first/last kv block of a q block
                        (accumulator init / output flush), 0 on the tail
      nitems          — [1] live item count
      pid[i*ppb + p]  — pool page behind slot ``p`` of item ``i`` (the
                        k/v window index maps read it).  Slots past
                        the sequence's last page repeat that page (the
                        kernel masks their tokens), and the budget tail
                        repeats the last live item's pages.
    """
    blk_tokens = pages_per_block * page_size
    kv_lens = kv_lens.astype(jnp.int32)
    q_lens = q_lens.astype(jnp.int32)
    nseq = q_lens.shape[0]
    nqb = _cdiv(q_lens, q_block)                     # [B] q blocks/seq
    cq = jnp.cumsum(nqb)
    total_qb = cq[-1]
    seg_blk = cq - nqb                               # seq -> first q block

    j = jnp.arange(nqb_total, dtype=jnp.int32)       # flat q-block axis
    seq_j = jnp.minimum(jnp.searchsorted(cq, j, side="right"),
                        nseq - 1).astype(jnp.int32)
    qb_j = j - seg_blk[seq_j]
    # causal truncation: q block qb only needs kv up to its last token's
    # position + 1 = kv_len - q_len + (qb+1)*q_block, clamped to kv_len
    kv_need = jnp.minimum(kv_lens[seq_j],
                          kv_lens[seq_j] - q_lens[seq_j]
                          + (qb_j + 1) * q_block)
    nk_j = jnp.where(j < total_qb, _cdiv(kv_need, blk_tokens), 0)
    ck = jnp.cumsum(nk_j)
    nitems = ck[-1]

    i = jnp.arange(item_budget, dtype=jnp.int32)     # flat item axis
    j_i = jnp.minimum(jnp.searchsorted(ck, i, side="right"),
                      nqb_total - 1).astype(jnp.int32)
    kb_i = i - (ck[j_i] - nk_j[j_i])
    seq_i = seq_j[j_i]
    qbg_i = seg_blk[seq_i] + qb_j[j_i]
    live = i < nitems
    last_qbg = qbg_i[jnp.maximum(nitems - 1, 0)]
    qbg_i = jnp.where(live, qbg_i, last_qbg)
    first_i = (live & (kb_i == 0)).astype(jnp.int32)
    last_i = (live & (kb_i == nk_j[j_i] - 1)).astype(jnp.int32)
    i_pg = jnp.where(live, i, jnp.maximum(nitems - 1, 0))
    last_pg = jnp.maximum(_cdiv(kv_lens[seq_i[i_pg]], page_size) - 1, 0)
    slot = jnp.minimum(
        kb_i[i_pg][:, None] * pages_per_block
        + jnp.arange(pages_per_block, dtype=jnp.int32)[None, :],
        last_pg[:, None])
    pid = block_tables.astype(jnp.int32)[seq_i[i_pg][:, None], slot]
    pid = jnp.clip(pid, 0, num_pages - 1)   # a window must name a page
    return (seq_i, qb_j[j_i].astype(jnp.int32), kb_i.astype(jnp.int32),
            qbg_i.astype(jnp.int32), first_i, last_i,
            jnp.reshape(nitems, (1,)).astype(jnp.int32),
            pid.reshape(-1))


def _count_items(kv_lens, q_lens, q_block, blk_tokens):
    """Exact live-item count for CONCRETE lengths (numpy) — eager calls
    size the grid tightly instead of paying the worst-case budget."""
    kv = np.asarray(kv_lens, np.int64)
    ql = np.asarray(q_lens, np.int64)
    total = 0
    for b in range(kv.shape[0]):
        for qb in range(int(_cdiv(ql[b], q_block))):
            need = min(kv[b], kv[b] - ql[b] + (qb + 1) * q_block)
            total += int(_cdiv(need, blk_tokens))
    return total


# --------------------------------------------------------------------------
# kernel
# --------------------------------------------------------------------------

def _ragged_kernel(seq_ref, qb_ref, kb_ref, qbg_ref, first_ref, last_ref,
                   nitems_ref, pid_ref, kvl_ref, ql_ref,
                   q_ref, *refs,
                   scale, page_size, pages_per_block, q_block, rep_p,
                   quant):
    """One compacted work item: ``pages_per_block`` pages of one
    sequence's kv block against one q block.  Scalars (prefetched):
    item maps + per-slot page ids + kv/q lengths [B].  q/o blocks:
    [1, 1, q_block*rep_p, D].  The k/v pools stay in HBM; ``refs``
    opens with one [page_size, D] window per page slot of k, then of v
    (``_ragged_call``'s index maps point each at its page).

    ``quant``: the pools are int8 and two per-page scale side-pools
    [Hk, P, page_size] ride along — each page's scale vector is
    windowed with its data page and the dequant (one VPU multiply per
    token row) happens right here in VMEM, so quantized attention reads
    a QUARTER of the fp32 KV bytes per step and never materializes a
    float page in HBM (PAPERS.md #3's fuse-dequant-into-the-consumer
    argument applied to the page fetch)."""
    del qbg_ref, pid_ref                        # index maps only
    n = pages_per_block
    k_refs, v_refs, refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    if quant:
        ks_refs, vs_refs, refs = refs[:n], refs[n:2 * n], refs[2 * n:]
    o_ref, m_s, l_s, acc_s = refs
    i = pl.program_id(1)
    live = i < nitems_ref[0]
    blk_tokens = pages_per_block * page_size

    @pl.when(first_ref[i] == 1)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def _block(page_refs):
        return jnp.concatenate([r[...] for r in page_refs], axis=0)

    @pl.when(live)
    def _accumulate():
        b = seq_ref[i]
        kb = kb_ref[i]
        kv_len = kvl_ref[b]

        q = q_ref[0, 0].astype(jnp.float32) * scale      # [rows, D]
        kblk = _block(k_refs).astype(jnp.float32)        # [blk_tokens, D]
        vblk = _block(v_refs).astype(jnp.float32)
        if quant:   # in-VMEM dequant: int8 row * its per-slot scale
            kblk = kblk * _block(ks_refs).reshape(blk_tokens, 1)
            vblk = vblk * _block(vs_refs).reshape(blk_tokens, 1)
        # slots past the sequence's last page repeat that page, and the
        # last page's tail was never written. Zero both BEFORE the
        # dots: the softmax mask alone is not enough (0-weight x NaN
        # garbage = NaN in the p@v accumulation).
        tok_valid = (kb * blk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, (blk_tokens, 1), 0)) < kv_len
        kblk = jnp.where(tok_valid, kblk, 0.0)
        vblk = jnp.where(tok_valid, vblk, 0.0)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # causal/ragged mask: q row r is token qb*q_block + r // rep_p
        # of its sequence, sitting at absolute position kv_len - q_len
        # + that index; kv column c is absolute position kb*blk + c.
        kv_pos = kb * blk_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        qi = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // rep_p
        q_pos = kv_len - ql_ref[b] + qb_ref[i] * q_block + qi
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)

        m_prev = m_s[:, 0:1]
        l_prev = l_s[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_ = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p_, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p_, vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(last_ref[i] == 1)
    def _finish():
        l = l_s[:, 0:1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


def _ragged_call(qx, k_pages, v_pages, kv_lens, q_lens, plan,
                 item_budget, *, scale, q_block, rep_p, pages_per_block,
                 interpret, k_scales=None, v_scales=None):
    """Shared pallas_call: ``qx`` is the blocked q layout
    [Hk, n_q_blocks, q_block*rep_p, D]; returns the same layout.
    ``k_scales``/``v_scales`` [Hk, P, page_size] switch on the int8
    in-kernel-dequant variant."""
    hk, nqb_total, rows, d = qx.shape
    page_size = k_pages.shape[2]
    grid = (hk, item_budget)
    quant = k_scales is not None
    kernel = functools.partial(
        _ragged_kernel, scale=float(scale), page_size=page_size,
        pages_per_block=pages_per_block, q_block=q_block, rep_p=rep_p,
        quant=quant)

    def q_index(ih, i, seq, qb, kb, qbg, first, last, nitems, pid, kvl,
                ql):
        return (ih, qbg[i], 0, 0)

    def page_specs(block, tail):
        """One window per page slot: slot ``p`` of item ``i`` is page
        ``pid[i * pages_per_block + p]`` of kv head ``ih``."""
        def spec(p):
            def index(ih, i, seq, qb, kb, qbg, first, last, nitems, pid,
                      kvl, ql):
                return (ih, pid[i * pages_per_block + p]) + tail
            return pl.BlockSpec(block, index)
        return [spec(p) for p in range(pages_per_block)]

    page = page_specs((None, None, page_size, d), (0, 0))
    in_specs = [pl.BlockSpec((1, 1, rows, d), q_index)] + page + page
    pools = [k_pages] * pages_per_block + [v_pages] * pages_per_block
    if quant:
        in_specs += 2 * page_specs((None, 1, page_size), (0,))
        pools += ([k_scales.astype(jnp.float32)] * pages_per_block
                  + [v_scales.astype(jnp.float32)] * pages_per_block)
    scratch = [
        pltpu.VMEM((rows, _LANE), jnp.float32),
        pltpu.VMEM((rows, _LANE), jnp.float32),
        pltpu.VMEM((rows, d), jnp.float32),
    ]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=10,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, rows, d), q_index),
            scratch_shapes=scratch,
        ),
        out_shape=out_struct(qx.shape, qx.dtype, qx, *pools),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*plan, kv_lens.astype(jnp.int32), q_lens.astype(jnp.int32), qx,
      *pools)


# --------------------------------------------------------------------------
# public entries
# --------------------------------------------------------------------------

def _resolve(interpret, scale, d):
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    return interpret, scale


def _is_concrete(*xs):
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, kv_lens,
                           q_lens, q_block=8, pages_per_block=None,
                           scale=None, interpret=None, item_budget=None,
                           k_scales=None, v_scales=None):
    """Attention for a continuously-batched step over a paged KV cache.

    q: [T, Hq, D] — tokens of ALL sequences packed in sequence order,
      each sequence's segment padded up to a multiple of ``q_block``
      (segment b starts at ``q_block * sum(ceil(q_lens[:b]/q_block))``);
    k_pages/v_pages: [Hk, total_pages, page_size, D] page pools — the
      new tokens' K/V must already be written to their (page, slot);
    block_tables: [B, pages_per_seq] int32 page ids per sequence;
    kv_lens: [B] total kv tokens per sequence INCLUDING this step's;
    q_lens: [B] tokens each sequence contributes this step (0 = sits
      out; decode rows 1; prefill rows the prompt-chunk length).
    k_scales/v_scales: [Hk, total_pages, page_size] f32 side-pools for
      INT8 pools (``quantization.kv_quantize`` layout): pages dequantize
      inside the kernel's DMA loop, so a quantized step moves a quarter
      of the fp32 KV bytes.  Both or neither.

    Returns [T, Hq, D] (rows of segment padding are garbage — callers
    gather real token rows only).  Mixed prefill+decode batches are the
    point: one call, one grid, per-sequence causal offsets.
    """
    if (k_scales is None) != (v_scales is None):
        raise ValueError("ragged_paged_attention: pass both k_scales "
                         "and v_scales or neither")
    t, hq, d = q.shape
    hk, _, page_size, _ = k_pages.shape
    if hk == 0 or hq % hk != 0:
        raise ValueError(f"ragged_paged_attention: {hq} q heads not a "
                         f"multiple of {hk} kv heads")
    rep = hq // hk
    rep_p = _row_pad(q_block, rep)
    npages = block_tables.shape[1]
    interpret, scale = _resolve(interpret, scale, d)
    if pages_per_block is None:
        pages_per_block = pick_pages_per_block(
            hk, page_size, d, npages, q_heads=hq)
    pages_per_block = max(1, min(int(pages_per_block), npages))
    blk_tokens = pages_per_block * page_size

    tp = _cdiv(t, q_block) * q_block
    if tp != t:
        q = jnp.pad(q, ((0, tp - t), (0, 0), (0, 0)))
    nqb_total = tp // q_block
    if item_budget is None:
        if _is_concrete(kv_lens, q_lens):
            item_budget = max(
                1, _count_items(kv_lens, q_lens, q_block, blk_tokens))
        else:
            item_budget = nqb_total * _cdiv(npages, pages_per_block)
    plan = _plan_items(jnp.asarray(kv_lens), jnp.asarray(q_lens),
                       jnp.asarray(block_tables), q_block=q_block,
                       page_size=page_size,
                       pages_per_block=pages_per_block,
                       num_pages=k_pages.shape[1],
                       nqb_total=nqb_total, item_budget=item_budget)

    qg = q.reshape(tp, hk, rep, d)
    if rep_p != rep:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rep_p - rep), (0, 0)))
    qx = jnp.transpose(qg, (1, 0, 2, 3)).reshape(
        hk, nqb_total, q_block * rep_p, d)

    out = _ragged_call(qx, k_pages, v_pages, jnp.asarray(kv_lens),
                       jnp.asarray(q_lens), plan, item_budget,
                       scale=scale, q_block=q_block, rep_p=rep_p,
                       pages_per_block=pages_per_block,
                       interpret=interpret, k_scales=k_scales,
                       v_scales=v_scales)
    out = out.reshape(hk, tp, rep_p, d)[:, :t, :rep]
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(t, hq, d)


def paged_decode_attention(q, k_pages, v_pages, block_tables, seq_lens,
                           scale=None, interpret=None,
                           pages_per_block=None, k_scales=None,
                           v_scales=None):
    """One decode step of attention over a paged KV cache.

    q: [B, Hq, D] (one query token per sequence);
    k_pages/v_pages: [Hk, total_pages, page_size, D] page pool;
    block_tables: [B, pages_per_seq] int32 — global page ids per
      sequence (may be traced: the serving engine re-points tables at
      admission without recompiling);
    seq_lens: [B] int32 — valid tokens (including the current one);
    k_scales/v_scales: int8-pool scale side-pools (see
      ``ragged_paged_attention``).
    Returns [B, Hq, D]. ``Hq`` must be a multiple of ``Hk`` (GQA).

    This is ``ragged_paged_attention`` with every sequence contributing
    one token (q_block=1): the B q blocks flatten onto the compacted
    grid, each program covering ``pages_per_block`` pages.
    """
    b = q.shape[0]
    return ragged_paged_attention(
        q, k_pages, v_pages, block_tables,
        jnp.asarray(seq_lens), jnp.ones((b,), jnp.int32),
        q_block=1, pages_per_block=pages_per_block, scale=scale,
        interpret=interpret, k_scales=k_scales, v_scales=v_scales)


# --------------------------------------------------------------------------
# pages_per_block selection (heuristic default + autotune)
# --------------------------------------------------------------------------

# ~512 kv tokens per grid step amortizes the q-block fetch and the
# per-program control overhead while 2 * ppb * page_size * D * 4B of
# scratch stays far under VMEM; capped by the table width.
_TARGET_BLK_TOKENS = 512
_VMEM_CAP_BYTES = 4 * 1024 * 1024


def default_pages_per_block(page_size, npages, head_dim):
    per_page = 2 * page_size * head_dim * 4
    cap = max(1, _VMEM_CAP_BYTES // max(per_page, 1))
    tgt = max(1, _TARGET_BLK_TOKENS // max(page_size, 1))
    p = 1
    while p * 2 <= min(tgt, npages, cap):
        p *= 2
    return p


def _tune_candidates(page_size, npages, head_dim):
    per_page = 2 * page_size * head_dim * 4
    cap = max(1, _VMEM_CAP_BYTES // max(per_page, 1))
    cands, p = [], 1
    while p <= min(npages, cap):
        cands.append(p)
        p *= 2
    return cands


def pick_pages_per_block(hk, page_size, head_dim, npages, q_heads=None):
    """``pages_per_block`` through the autotune cache (SURVEY C14).
    Cache hits apply everywhere (including under a trace — the key is
    static); the measuring sweep runs only when autotuning is enabled,
    on synthetic decode shapes, so a first serving call never stalls."""
    from . import autotune as at
    cands = _tune_candidates(page_size, npages, head_dim)
    fallback = default_pages_per_block(page_size, npages, head_dim)
    if len(cands) <= 1:
        return fallback
    sig = f"hk{hk}_ps{page_size}_d{head_dim}_np{npages}"
    try:
        cached = at._load_cache().get(
            f"{at._device_kind()}|paged_attention_ppb|{sig}")
    except Exception:
        cached = None
    if cached is not None and cached in cands:
        return int(cached)
    if not at.enabled():
        return fallback

    hq = q_heads or hk
    b = 4
    rng = np.random.default_rng(0)
    qs = jnp.asarray(rng.normal(size=(b, hq, head_dim)), jnp.float32)
    pool = jnp.asarray(rng.normal(
        size=(hk, b * npages, page_size, head_dim)), jnp.float32)
    bt = jnp.arange(b * npages, dtype=jnp.int32).reshape(b, npages)
    lens = jnp.full((b,), npages * page_size, jnp.int32)

    def run(cand):
        out = paged_decode_attention(qs, pool, pool, bt, lens,
                                     pages_per_block=int(cand))
        jax.block_until_ready(out)

    try:
        return int(at.autotune("paged_attention_ppb", sig, cands, run))
    except Exception:
        return fallback
