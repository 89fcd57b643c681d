"""Fused decode-ingress Pallas kernel (ISSUE 18 tentpole, kernel 1/2):
norm (LayerNorm or RMSNorm) + fused-QKV projection + RoPE + paged-KV
append in ONE dispatch per decode layer.

Small-batch decode is dispatch-bound, not FLOP-bound (serving_bench r05:
paged_b1 82.6 tok/s vs dense 110.5 with launch_share attributing the gap
to ~10 launches per layer), so the whole token-ingress chain that
today runs as norm -> matmul -> (+bias) -> rope -> swap -> quantize ->
two/four page scatters collapses into a single ``pl.pallas_call``:

* the block math (``_qkv_block``) replays the EXACT op order of the
  unfused path — ``nn.functional.norm`` jnp moments, one fused or three
  separate ``jnp.matmul`` projections, ``models.llama.rope_angles``
  (the single home of the rope convention) with rotate-half — so fused
  and unfused activations are bitwise-identical, not just close;
* the paged-KV append reuses ``quantization.kv_quantize`` verbatim for
  int8 pools, so the bytes landing in the pools equal the unfused
  ``_slot_page_write`` path byte-for-byte;
* pools ride through ``memory_space=ANY`` refs aliased in-place
  (``input_output_aliases``), and each row's (page, slot) target —
  looked up from scalar-prefetched positions/block-tables, the
  block-tables-as-data contract that keeps serving recompile-free —
  is written with a small VMEM-staged ``make_async_copy``.

Following the PR4/PR7/PR11 fused-kernel discipline, the unjitted jnp
twin (``fused_decode_qkv_twin``) replays the identical row-block walk
(same padding, same block math, same per-row write order) so
Pallas-interpret parity is BITWISE on every geometry; the row block is
an autotune entry (``fused_decode_qkv_rows`` — ``pick_qkv_rows``).

Note: norm parity is vs the functional jnp norm (the decode bodies'
default everywhere, including TPU unless PDTPU_NORM_BACKEND=pallas
reroutes norms to the standalone fused-norm kernels).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import out_struct


def _rotate_half(x, cos, sin):
    """models.llama rope application (generation._apply_rope body)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def _norm_block(xv, nw, nb, norm, eps):
    """The functional-layer norm math (nn/functional/norm.py `_moments`
    + apply order), shared by both decode megakernels and their twins.
    ``nw``/``nb`` arrive as [1, H]; bias applies ONLY when present
    (adding 0.0 would flip -0.0 -> +0.0 and break bitwise parity)."""
    v32 = xv.astype(jnp.float32)
    if norm == "layer":
        mean = jnp.mean(v32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(v32), axis=-1, keepdims=True) - \
            jnp.square(mean)
        out = (xv.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + eps)
        out = out.astype(xv.dtype)
    else:
        ms = jnp.mean(jnp.square(v32), axis=-1, keepdims=True)
        out = (v32 * jax.lax.rsqrt(ms + eps)).astype(xv.dtype)
    out = out * nw
    if nb is not None:
        out = out + nb
    return out


def _mm(a, b):
    """``a @ b`` accumulated in f32 and returned in ``a``'s dtype: Mosaic
    takes no matmul whose accumulator is narrower than 32 bits (the
    same bytes as ``jnp.matmul`` for f32 operands)."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(
        a.dtype)


def _qkv_block(xv, posv, nw, nb, ws, bs, *, norm, eps, n_heads,
               n_kv_heads, head_dim, rope_theta):
    """One row-block of the fused ingress math: norm -> QKV projection
    (one fused [H, (q+2kv)] weight in GPT column order [3, nh, hd], or
    three separate llama weights) -> rope -> head-major K/V.  Returns
    (q [rows, nh, hd], k [hk, rows, hd], v [hk, rows, hd]).  Kernel and
    twin both call THIS function — parity is by construction."""
    rows = xv.shape[0]
    h = _norm_block(xv, nw, nb, norm, eps)
    nq, nk = n_heads * head_dim, n_kv_heads * head_dim
    if len(ws) == 1:
        qkv = _mm(h, ws[0])
        if bs:
            qkv = qkv + bs[0]
        # row-major column slices == reshape([rows, 3, nh, hd]) unbind
        q = qkv[:, :nq]
        k = qkv[:, nq:nq + nk]
        v = qkv[:, nq + nk:]
    else:
        q = _mm(h, ws[0])
        k = _mm(h, ws[1])
        v = _mm(h, ws[2])
        if bs:
            q = q + bs[0]
            k = k + bs[1]
            v = v + bs[2]
    q = q.reshape(rows, n_heads, head_dim)
    k = k.reshape(rows, n_kv_heads, head_dim)
    v = v.reshape(rows, n_kv_heads, head_dim)
    if rope_theta is not None:
        from ...models.llama import rope_angles
        cos, sin = rope_angles(posv.reshape(-1), head_dim, rope_theta)
        cos, sin = cos[:, None, :], sin[:, None, :]
        q = _rotate_half(q, cos, sin)
        k = _rotate_half(k, cos, sin)
    # head-major like the page pools (generation's swapaxes convention)
    return q, jnp.swapaxes(k, 0, 1), jnp.swapaxes(v, 0, 1)


def _quantize_or_cast(kt, vt, quant, k_dtype, v_dtype):
    """Pool bytes: ``quantization.kv_quantize`` verbatim (int8 pools) or
    the unfused path's plain ``.astype`` (fp/bf16 pools)."""
    if quant:
        from ...quantization import kv_quantize
        kq, ksc = kv_quantize(kt)
        vq, vsc = kv_quantize(vt)
        return kq, vq, ksc, vsc
    return kt.astype(k_dtype), vt.astype(v_dtype), None, None


def _page_slot(pos_s, bt_s, gr, page_size, npages):
    """(page, slot) for global row ``gr`` — generation._slot_page_write's
    lookup: clamp past-the-table positions onto the last page."""
    p = pos_s[gr]
    page = bt_s[gr, jnp.minimum(p // page_size, npages - 1)]
    return page, p % page_size


def _qkv_kernel(*refs, layout, cfg, rows, n_valid, quant):
    """Pallas body.  refs = 2 scalar-prefetch (positions, block tables)
    + regular inputs + outputs + scratch, unpacked per ``layout``."""
    (i_x, i_posv, i_nw, i_nb, i_ws, i_bs, i_kp, o_q, o_kp, o_vp,
     o_ks, o_vs, s_kb, s_vb, s_ksb, s_vsb, s_sem) = layout
    pos_s, bt_s = refs[0], refs[1]
    nb = refs[i_nb][...] if i_nb is not None else None
    q, kt, vt = _qkv_block(
        refs[i_x][...], refs[i_posv][...], refs[i_nw][...], nb,
        [refs[j][...] for j in i_ws], [refs[j][...] for j in i_bs],
        **cfg)
    refs[o_q][...] = q
    kq, vq, ksc, vsc = _quantize_or_cast(
        kt, vt, quant, refs[o_kp].dtype, refs[o_vp].dtype)
    page_size = refs[o_kp].shape[2]
    npages = bt_s.shape[1]
    base = pl.program_id(0) * rows
    sem = refs[s_sem]
    for r in range(rows):
        gr = base + r
        refs[s_kb][...] = kq[:, r:r + 1, :]
        refs[s_vb][...] = vq[:, r:r + 1, :]
        if quant:
            refs[s_ksb][...] = ksc[:, r:r + 1]
            refs[s_vsb][...] = vsc[:, r:r + 1]
        page, slot = _page_slot(pos_s, bt_s, gr, page_size, npages)
        copies = [(s_kb, o_kp), (s_vb, o_vp)]
        if quant:
            copies += [(s_ksb, o_ks), (s_vsb, o_vs)]

        def _write(copies=copies, page=page, slot=slot):
            for src, dst in copies:
                cp = pltpu.make_async_copy(
                    refs[src].at[...],
                    refs[dst].at[:, page, pl.ds(slot, 1)], sem)
                cp.start()
                cp.wait()

        pl.when(gr < n_valid)(_write)


def _prep(x, norm_w, norm_b, weights, biases, positions, block_tables,
          rows):
    """Shared wrapper/twin preamble: row-block size, padding, [1, H]
    param layouts.  The twin replays this verbatim."""
    b, h = x.shape
    rows_c = b if rows is None else int(rows)
    bp = ((b + rows_c - 1) // rows_c) * rows_c
    pad = bp - b
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        positions = jnp.pad(positions, (0, pad))
        block_tables = jnp.pad(block_tables, ((0, pad), (0, 0)))
    posp = positions.astype(jnp.int32)
    btp = block_tables.astype(jnp.int32)
    nw = norm_w.reshape(1, h)
    nb = norm_b.reshape(1, h) if norm_b is not None else None
    ws = [jnp.asarray(w) for w in weights]
    bs = [jnp.asarray(bi).reshape(1, -1) for bi in biases]
    return x, posp, btp, nw, nb, ws, bs, rows_c, bp


def fused_decode_qkv(x, norm_w, norm_b, weights, biases, positions,
                     block_tables, k_pages, v_pages, k_scales=None,
                     v_scales=None, *, norm="layer", eps=1e-5, n_heads,
                     n_kv_heads, head_dim, rope_theta=None, rows=None,
                     interpret=None):
    """Fused norm+QKV+rope+paged-append for one decode step.

    x: [B, H] token hidden states; weights: ONE fused [H, (nh+2*hk)*hd]
    projection (GPT column order [3, nh, hd]) or three separate
    (wq, wk, wv); biases: matching list or empty.  positions [B] i32,
    block_tables [B, NP] i32.  Pools are head-major [Hk, P, ps, D]
    (+ [Hk, P, ps] scale pools when quantized) and are updated
    IN-PLACE via input_output_aliases.  Returns
    (q [B, nh, hd], k_pages, v_pages[, k_scales, v_scales]).
    """
    if interpret is None:
        from . import use_interpret
        interpret = use_interpret()
    b, h = x.shape
    quant = k_scales is not None
    xp, posp, btp, nw, nb, ws, bs, rows_c, bp = _prep(
        x, norm_w, norm_b, weights, biases, positions, block_tables,
        rows)
    cfg = dict(norm=norm, eps=eps, n_heads=n_heads,
               n_kv_heads=n_kv_heads, head_dim=head_dim,
               rope_theta=rope_theta)
    q_abs, _, _ = jax.eval_shape(
        functools.partial(_qkv_block, **cfg),
        jax.ShapeDtypeStruct((rows_c, h), xp.dtype),
        jax.ShapeDtypeStruct((rows_c, 1), jnp.int32),
        jax.ShapeDtypeStruct((1, h), nw.dtype),
        None if nb is None else jax.ShapeDtypeStruct((1, h), nb.dtype),
        [jax.ShapeDtypeStruct(w.shape, w.dtype) for w in ws],
        [jax.ShapeDtypeStruct(bi.shape, bi.dtype) for bi in bs])

    # regular-input layout (indices are into the kernel's full ref list:
    # 2 scalar-prefetch refs first, then inputs, outputs, scratch)
    row_spec = pl.BlockSpec((rows_c, h), lambda i, *_: (i, 0))
    one_spec = pl.BlockSpec((1, h), lambda i, *_: (0, 0))
    full = functools.partial(pl.BlockSpec,
                             index_map=lambda i, *_: (0, 0))
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    args = [xp, posp[:, None], nw]
    in_specs = [row_spec, pl.BlockSpec((rows_c, 1), lambda i, *_: (i, 0)),
                one_spec]
    i_x, i_posv, i_nw = 2, 3, 4
    i_nb = None
    if nb is not None:
        i_nb = 2 + len(args)
        args.append(nb)
        in_specs.append(one_spec)
    i_ws = []
    for w in ws:
        i_ws.append(2 + len(args))
        args.append(w)
        in_specs.append(full(w.shape))
    i_bs = []
    for bi in bs:
        i_bs.append(2 + len(args))
        args.append(bi)
        in_specs.append(full(bi.shape))
    i_kp = 2 + len(args)
    pools = [k_pages, v_pages] + ([k_scales, v_scales] if quant else [])
    args += pools
    in_specs += [any_spec] * len(pools)
    n_in = 2 + len(args)

    out_shape = [out_struct((bp, n_heads, head_dim), q_abs.dtype, *args)]
    out_shape += [out_struct(p.shape, p.dtype, *args) for p in pools]
    out_specs = [pl.BlockSpec((rows_c, n_heads, head_dim),
                              lambda i, *_: (i, 0, 0))]
    out_specs += [any_spec] * len(pools)
    o_q = n_in
    o_kp, o_vp = n_in + 1, n_in + 2
    o_ks = n_in + 3 if quant else None
    o_vs = n_in + 4 if quant else None
    n_out = 1 + len(pools)

    scratch = [pltpu.VMEM((n_kv_heads, 1, head_dim), k_pages.dtype),
               pltpu.VMEM((n_kv_heads, 1, head_dim), v_pages.dtype)]
    s_kb, s_vb = n_in + n_out, n_in + n_out + 1
    s_ksb = s_vsb = None
    if quant:
        scratch += [pltpu.VMEM((n_kv_heads, 1), k_scales.dtype),
                    pltpu.VMEM((n_kv_heads, 1), v_scales.dtype)]
        s_ksb, s_vsb = s_vb + 1, s_vb + 2
    scratch.append(pltpu.SemaphoreType.DMA)
    s_sem = n_in + n_out + len(scratch) - 1

    layout = (i_x, i_posv, i_nw, i_nb, tuple(i_ws), tuple(i_bs), i_kp,
              o_q, o_kp, o_vp, o_ks, o_vs, s_kb, s_vb, s_ksb, s_vsb,
              s_sem)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(bp // rows_c,),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch)
    aliases = {i_kp + j: 1 + j for j in range(len(pools))}
    outs = pl.pallas_call(
        functools.partial(_qkv_kernel, layout=layout, cfg=cfg,
                          rows=rows_c, n_valid=b, quant=quant),
        grid_spec=grid_spec, out_shape=out_shape,
        input_output_aliases=aliases, interpret=bool(interpret),
    )(posp, btp, *args)
    return (outs[0][:b],) + tuple(outs[1:])


def fused_decode_qkv_twin(x, norm_w, norm_b, weights, biases, positions,
                          block_tables, k_pages, v_pages, k_scales=None,
                          v_scales=None, *, norm="layer", eps=1e-5,
                          n_heads, n_kv_heads, head_dim, rope_theta=None,
                          rows=None, interpret=None):
    """jnp twin outside any pallas_call: replays the kernel's exact
    row-block walk — same padding, same ``_qkv_block`` math, same
    per-row quantize/cast and (page, slot) write order — so
    interpret-mode kernel output matches BITWISE on every geometry.
    The per-block math runs under ``jax.jit`` so both sides share
    XLA's elementwise-fusion (FMA) semantics — op-by-op eager
    execution drifts ~1 ulp on the norm scale/shift and rope chains.
    ``interpret`` accepted/ignored so the two functions are
    call-compatible."""
    del interpret
    b, h = x.shape
    quant = k_scales is not None
    xp, posp, btp, nw, nb, ws, bs, rows_c, bp = _prep(
        x, norm_w, norm_b, positions=positions,
        block_tables=block_tables, weights=weights, biases=biases,
        rows=rows)
    cfg = dict(norm=norm, eps=eps, n_heads=n_heads,
               n_kv_heads=n_kv_heads, head_dim=head_dim,
               rope_theta=rope_theta)
    blk = jax.jit(functools.partial(_qkv_block, **cfg))
    quantize = jax.jit(functools.partial(
        _quantize_or_cast, quant=quant, k_dtype=k_pages.dtype,
        v_dtype=v_pages.dtype))
    kp, vp, ks, vs = k_pages, v_pages, k_scales, v_scales
    page_size, npages = kp.shape[2], btp.shape[1]
    q_blocks = []
    for i in range(bp // rows_c):
        sl = slice(i * rows_c, (i + 1) * rows_c)
        q, kt, vt = blk(xp[sl], posp[sl, None], nw, nb, ws, bs)
        q_blocks.append(q)
        kq, vq, ksc, vsc = quantize(kt, vt)
        for r in range(rows_c):
            gr = i * rows_c + r
            if gr >= b:
                continue
            p = int(posp[gr])
            page = int(btp[gr, min(p // page_size, npages - 1)])
            slot = p % page_size
            kp = kp.at[:, page, slot].set(kq[:, r])
            vp = vp.at[:, page, slot].set(vq[:, r])
            if quant:
                ks = ks.at[:, page, slot].set(ksc[:, r])
                vs = vs.at[:, page, slot].set(vsc[:, r])
    q = jnp.concatenate(q_blocks, axis=0)[:b]
    return (q, kp, vp) + ((ks, vs) if quant else ())


# --------------------------------------------------------------------------
# autotune entry: fused_decode_qkv_rows
# --------------------------------------------------------------------------
_ROW_CANDIDATES = (4, 8, 16, 32, 64, 128)
_VMEM_CAP_BYTES = 4 * 1024 * 1024


def _row_candidates(b, hidden, width):
    """Row blocks whose activation tiles fit the VMEM cap (weights are
    resident regardless — the megakernel targets decode hidden sizes,
    not giant projection widths)."""
    cands = [c for c in _ROW_CANDIDATES if c <= max(b, 4)
             and c * (hidden + width) * 4 <= _VMEM_CAP_BYTES]
    return cands


def default_rows(b):
    """Whole batch in one block: decode batches are small and a single
    block keeps the matmul M-dim equal to the unfused path's."""
    return b


def pick_qkv_rows(b, hidden, n_heads, n_kv_heads, head_dim):
    """Row block for fused_decode_qkv through the autotune cache
    (entry ``fused_decode_qkv_rows``).  Cache hits apply everywhere;
    the measuring sweep runs on synthetic shapes only when autotuning
    is enabled, so a first serving call never stalls."""
    import numpy as np
    from . import autotune as at
    width = (n_heads + 2 * n_kv_heads) * head_dim
    cands = _row_candidates(b, hidden, width)
    fallback = default_rows(b)
    if len(cands) <= 1:
        return fallback
    sig = f"b{b}_h{hidden}_nh{n_heads}_hk{n_kv_heads}_d{head_dim}"
    try:
        cached = at._load_cache().get(
            f"{at._device_kind()}|fused_decode_qkv_rows|{sig}")
    except Exception:
        cached = None
    if cached is not None and cached in cands:
        return int(cached)
    if not at.enabled():
        return fallback

    rng = np.random.default_rng(0)
    npages, ps = 4, 8
    x = jnp.asarray(rng.normal(size=(b, hidden)), jnp.float32)
    nw = jnp.ones((hidden,), jnp.float32)
    w = jnp.asarray(rng.normal(size=(hidden, width)) * 0.02, jnp.float32)
    pos = jnp.arange(b, dtype=jnp.int32)
    bt = jnp.arange(b * npages, dtype=jnp.int32).reshape(b, npages)
    pool = jnp.zeros((n_kv_heads, b * npages, ps, head_dim), jnp.float32)

    def run(cand):
        out = fused_decode_qkv(
            x, nw, None, [w], [], pos, bt, pool, pool,
            norm="rms", eps=1e-6, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, rows=int(cand))
        jax.block_until_ready(out)

    try:
        return int(at.autotune("fused_decode_qkv_rows", sig, cands, run))
    except Exception:
        return fallback
