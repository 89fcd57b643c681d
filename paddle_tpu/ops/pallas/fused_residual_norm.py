"""Fused residual-add + normalization training glue kernels (ISSUE 19).

The training step's per-layer glue chain — residual add feeding a
pre/post-norm — sits between the flash and matmul kernels as separate
dispatches (the step's glue share).  Each kernel here
runs one row-blocked pass computing BOTH the residual sum and its
normalized value, saving fp32 stats for a fused backward that replays
the exact tile walk (the ``flash_attention_bwd_jnp`` discipline):

  ``fused_residual_layer_norm``  (res, normed) = (x+y, LN(x+y)*w+b)
  ``fused_residual_rms_norm``    (res, normed) = (x+y, RMS(x+y)*w)

Both are ``jax.custom_vjp``: the backward kernel consumes the residual
stream cotangent AND the normed cotangent in one pass and emits the
shared input cotangent (d(x) == d(y)) plus tile-aligned dw/db partials
summed on the host, exactly like ``norms.py``.

Every kernel has an unjitted twin (``*_fwd_twin`` / ``*_bwd_twin``)
walking identical row blocks with the block math under ``jax.jit`` —
bitwise vs interpret mode.  Row block is an autotune entry
(``fused_residual_norm_rows`` — ``pick_glue_rows``).

Wired into the GPT/LLaMA/BERT blocks behind the ``train_glue_fusion``
flag (default OFF: a custom call is a fusion BARRIER, and the
standalone Pallas LN lost time in context for that reason, see
nn/functional/norm.py — so the fused glue path ships dark until a
cell prices it end to end).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def default_rows(rows):
    return min(256, rows)


def _pad_rows(x, br):
    pad = (-x.shape[0]) % br
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _resolve_interpret(interpret):
    if interpret is None:
        from . import use_interpret
        return use_interpret()
    return bool(interpret)


# --------------------------------------------------------------------------
# block math — shared VERBATIM by the Pallas kernels (on loaded tiles)
# and the jnp twins (jitted per row block), so parity is structural
# --------------------------------------------------------------------------
def _rln_fwd_block(xv, yv, w, b, *, eps):
    """One row tile: residual add (input dtype, the blocks' op order),
    then LayerNorm with fp32 stats.  Returns (res, normed, mean, rstd)."""
    r = xv + yv
    r32 = r.astype(jnp.float32)
    mean = jnp.mean(r32, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(r32 - mean), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o = ((r32 - mean) * rstd * w.astype(jnp.float32)
         + b.astype(jnp.float32)).astype(r.dtype)
    return r, o, mean, rstd


def _rln_bwd_block(rv, w, mean, rstd, drv, gv, *, eps):
    """One row tile of the fused backward: d = dres + LN_dx(dnormed),
    the SHARED cotangent of both adders (d(x) == d(y) == d), plus this
    tile's dw/db partials (fp32 row sums)."""
    del eps  # stats are saved; eps only shapes them in forward
    r32 = rv.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    g = gv.astype(jnp.float32)
    dr = drv.astype(jnp.float32)
    xhat = (r32 - mean) * rstd
    wg = g * w32
    c1 = jnp.mean(wg, axis=1, keepdims=True)
    c2 = jnp.mean(wg * xhat, axis=1, keepdims=True)
    d = (dr + rstd * (wg - c1 - xhat * c2)).astype(rv.dtype)
    return d, jnp.sum(g * xhat, axis=0), jnp.sum(g, axis=0)


def _rrms_fwd_block(xv, yv, w, *, eps):
    r = xv + yv
    r32 = r.astype(jnp.float32)
    ms = jnp.mean(jnp.square(r32), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    o = (r32 * rstd * w.astype(jnp.float32)).astype(r.dtype)
    return r, o, rstd


def _rrms_bwd_block(rv, w, rstd, drv, gv, *, eps):
    del eps
    r32 = rv.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    g = gv.astype(jnp.float32)
    dr = drv.astype(jnp.float32)
    xhat = r32 * rstd
    wg = g * w32
    c = jnp.mean(wg * xhat, axis=1, keepdims=True)
    d = (dr + rstd * (wg - xhat * c)).astype(rv.dtype)
    return d, jnp.sum(g * xhat, axis=0)


# --------------------------------------------------------------------------
# kernel/twin plumbing (row-blocked; weights ride block-invariant)
# --------------------------------------------------------------------------
def _rows_for(n_valid, rows):
    return default_rows(n_valid) if rows is None else int(rows)


def _row_spec(br, h):
    return pl.BlockSpec((br, h), lambda i: (i, 0))


def _stat_spec(br):
    return pl.BlockSpec((br, 1), lambda i: (i, 0))


def _full_spec(h):
    return pl.BlockSpec((1, h), lambda i: (0, 0))


def _tile_spec(h):
    # tile-aligned (grid, 8, h) partial accumulator (norms.py layout)
    return pl.BlockSpec((1, 8, h), lambda i: (i, 0, 0))


def fused_residual_layer_norm_fwd(x, y, w, b, *, eps=1e-5, rows=None,
                                  interpret=None):
    """Kernel forward on 2-D [rows, h]: (res, normed, mean, rstd)."""
    n, h = x.shape
    br = _rows_for(n, rows)
    xp, yp = _pad_rows(x, br), _pad_rows(y, br)
    grid = (xp.shape[0] // br,)

    def kernel(x_ref, y_ref, w_ref, b_ref, r_ref, o_ref, m_ref, s_ref):
        r, o, mean, rstd = _rln_fwd_block(
            x_ref[:], y_ref[:], w_ref[:], b_ref[:], eps=eps)
        r_ref[:] = r
        o_ref[:] = o
        m_ref[:] = mean
        s_ref[:] = rstd

    r, o, mean, rstd = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[_row_spec(br, h), _row_spec(br, h),
                  _full_spec(h), _full_spec(h)],
        out_specs=[_row_spec(br, h), _row_spec(br, h),
                   _stat_spec(br), _stat_spec(br)],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
                   jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32)],
        interpret=_resolve_interpret(interpret),
    )(xp, yp, w[None, :], b[None, :])
    return r[:n], o[:n], mean[:n], rstd[:n]


def fused_residual_layer_norm_fwd_twin(x, y, w, b, *, eps=1e-5,
                                       rows=None):
    """Twin of the forward kernel: identical padding, identical per-block
    math under ``jax.jit`` (shared FMA-fusion semantics), concatenated
    back — bitwise vs interpret mode."""
    n, h = x.shape
    br = _rows_for(n, rows)
    xp, yp = _pad_rows(x, br), _pad_rows(y, br)
    jfn = jax.jit(functools.partial(_rln_fwd_block, eps=eps))
    parts = [jfn(xp[i * br:(i + 1) * br], yp[i * br:(i + 1) * br],
                 w[None, :], b[None, :])
             for i in range(xp.shape[0] // br)]
    return tuple(jnp.concatenate(ps, axis=0)[:n] for ps in zip(*parts))


def fused_residual_layer_norm_bwd(res, w, mean, rstd, dres, dnormed, *,
                                  eps=1e-5, rows=None, interpret=None):
    """Kernel backward replaying the forward's tile walk: (d, dw, db)
    with d the SHARED x/y cotangent."""
    n, h = res.shape
    br = _rows_for(n, rows)
    rp = _pad_rows(res, br)
    pad = rp.shape[0] - n
    mp = jnp.pad(mean, ((0, pad), (0, 0)))
    sp = jnp.pad(rstd, ((0, pad), (0, 0)))
    drp, gp = _pad_rows(dres, br), _pad_rows(dnormed, br)
    grid = (rp.shape[0] // br,)

    def kernel(r_ref, w_ref, m_ref, s_ref, dr_ref, g_ref,
               d_ref, dwp_ref, dbp_ref):
        d, dw_p, db_p = _rln_bwd_block(
            r_ref[:], w_ref[:], m_ref[:], s_ref[:], dr_ref[:], g_ref[:],
            eps=eps)
        d_ref[:] = d
        dwp_ref[0] = jnp.broadcast_to(dw_p[None, :], (8, h))
        dbp_ref[0] = jnp.broadcast_to(db_p[None, :], (8, h))

    d, dwp, dbp = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[_row_spec(br, h), _full_spec(h), _stat_spec(br),
                  _stat_spec(br), _row_spec(br, h), _row_spec(br, h)],
        out_specs=[_row_spec(br, h), _tile_spec(h), _tile_spec(h)],
        out_shape=[jax.ShapeDtypeStruct(rp.shape, res.dtype),
                   jax.ShapeDtypeStruct((grid[0], 8, h), jnp.float32),
                   jax.ShapeDtypeStruct((grid[0], 8, h), jnp.float32)],
        interpret=_resolve_interpret(interpret),
    )(rp, w[None, :], mp, sp, drp, gp)
    return (d[:n], jnp.sum(dwp[:, 0], axis=0).astype(w.dtype),
            jnp.sum(dbp[:, 0], axis=0).astype(w.dtype))


def fused_residual_layer_norm_bwd_twin(res, w, mean, rstd, dres,
                                       dnormed, *, eps=1e-5, rows=None):
    """Backward twin replaying the EXACT tile walk (per-block jitted
    math, per-block dw/db partials, host sum in the kernel's order)."""
    n, h = res.shape
    br = _rows_for(n, rows)
    rp = _pad_rows(res, br)
    pad = rp.shape[0] - n
    mp = jnp.pad(mean, ((0, pad), (0, 0)))
    sp = jnp.pad(rstd, ((0, pad), (0, 0)))
    drp, gp = _pad_rows(dres, br), _pad_rows(dnormed, br)
    jfn = jax.jit(functools.partial(_rln_bwd_block, eps=eps))
    ds, dws, dbs = [], [], []
    for i in range(rp.shape[0] // br):
        sl = slice(i * br, (i + 1) * br)
        d, dw_p, db_p = jfn(rp[sl], w[None, :], mp[sl], sp[sl],
                            drp[sl], gp[sl])
        ds.append(d)
        dws.append(dw_p)
        dbs.append(db_p)
    return (jnp.concatenate(ds, axis=0)[:n],
            jnp.sum(jnp.stack(dws), axis=0).astype(w.dtype),
            jnp.sum(jnp.stack(dbs), axis=0).astype(w.dtype))


def fused_residual_rms_norm_fwd(x, y, w, *, eps=1e-6, rows=None,
                                interpret=None):
    """Kernel forward on 2-D [rows, h]: (res, normed, rstd)."""
    n, h = x.shape
    br = _rows_for(n, rows)
    xp, yp = _pad_rows(x, br), _pad_rows(y, br)
    grid = (xp.shape[0] // br,)

    def kernel(x_ref, y_ref, w_ref, r_ref, o_ref, s_ref):
        r, o, rstd = _rrms_fwd_block(x_ref[:], y_ref[:], w_ref[:],
                                     eps=eps)
        r_ref[:] = r
        o_ref[:] = o
        s_ref[:] = rstd

    r, o, rstd = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[_row_spec(br, h), _row_spec(br, h), _full_spec(h)],
        out_specs=[_row_spec(br, h), _row_spec(br, h), _stat_spec(br)],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct(xp.shape, x.dtype),
                   jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32)],
        interpret=_resolve_interpret(interpret),
    )(xp, yp, w[None, :])
    return r[:n], o[:n], rstd[:n]


def fused_residual_rms_norm_fwd_twin(x, y, w, *, eps=1e-6, rows=None):
    n, h = x.shape
    br = _rows_for(n, rows)
    xp, yp = _pad_rows(x, br), _pad_rows(y, br)
    jfn = jax.jit(functools.partial(_rrms_fwd_block, eps=eps))
    parts = [jfn(xp[i * br:(i + 1) * br], yp[i * br:(i + 1) * br],
                 w[None, :])
             for i in range(xp.shape[0] // br)]
    return tuple(jnp.concatenate(ps, axis=0)[:n] for ps in zip(*parts))


def fused_residual_rms_norm_bwd(res, w, rstd, dres, dnormed, *,
                                eps=1e-6, rows=None, interpret=None):
    n, h = res.shape
    br = _rows_for(n, rows)
    rp = _pad_rows(res, br)
    sp = jnp.pad(rstd, ((0, rp.shape[0] - n), (0, 0)))
    drp, gp = _pad_rows(dres, br), _pad_rows(dnormed, br)
    grid = (rp.shape[0] // br,)

    def kernel(r_ref, w_ref, s_ref, dr_ref, g_ref, d_ref, dwp_ref):
        d, dw_p = _rrms_bwd_block(r_ref[:], w_ref[:], s_ref[:],
                                  dr_ref[:], g_ref[:], eps=eps)
        d_ref[:] = d
        dwp_ref[0] = jnp.broadcast_to(dw_p[None, :], (8, h))

    d, dwp = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[_row_spec(br, h), _full_spec(h), _stat_spec(br),
                  _row_spec(br, h), _row_spec(br, h)],
        out_specs=[_row_spec(br, h), _tile_spec(h)],
        out_shape=[jax.ShapeDtypeStruct(rp.shape, res.dtype),
                   jax.ShapeDtypeStruct((grid[0], 8, h), jnp.float32)],
        interpret=_resolve_interpret(interpret),
    )(rp, w[None, :], sp, drp, gp)
    return d[:n], jnp.sum(dwp[:, 0], axis=0).astype(w.dtype)


def fused_residual_rms_norm_bwd_twin(res, w, rstd, dres, dnormed, *,
                                     eps=1e-6, rows=None):
    n, h = res.shape
    br = _rows_for(n, rows)
    rp = _pad_rows(res, br)
    sp = jnp.pad(rstd, ((0, rp.shape[0] - n), (0, 0)))
    drp, gp = _pad_rows(dres, br), _pad_rows(dnormed, br)
    jfn = jax.jit(functools.partial(_rrms_bwd_block, eps=eps))
    ds, dws = [], []
    for i in range(rp.shape[0] // br):
        sl = slice(i * br, (i + 1) * br)
        d, dw_p = jfn(rp[sl], w[None, :], sp[sl], drp[sl], gp[sl])
        ds.append(d)
        dws.append(dw_p)
    return (jnp.concatenate(ds, axis=0)[:n],
            jnp.sum(jnp.stack(dws), axis=0).astype(w.dtype))


# --------------------------------------------------------------------------
# differentiable public entries (custom_vjp; [..., h] inputs)
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _rln2d(x, y, w, b, eps, rows, interpret):
    r, o, _, _ = fused_residual_layer_norm_fwd(
        x, y, w, b, eps=eps, rows=rows, interpret=interpret)
    return r, o


def _rln2d_fwd(x, y, w, b, eps, rows, interpret):
    r, o, mean, rstd = fused_residual_layer_norm_fwd(
        x, y, w, b, eps=eps, rows=rows, interpret=interpret)
    return (r, o), (r, w, mean, rstd)


def _rln2d_bwd(eps, rows, interpret, saved, ct):
    r, w, mean, rstd = saved
    dres, dnormed = ct
    d, dw, db = fused_residual_layer_norm_bwd(
        r, w, mean, rstd, dres, dnormed, eps=eps, rows=rows,
        interpret=interpret)
    return d, d, dw, db


_rln2d.defvjp(_rln2d_fwd, _rln2d_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rrms2d(x, y, w, eps, rows, interpret):
    r, o, _ = fused_residual_rms_norm_fwd(
        x, y, w, eps=eps, rows=rows, interpret=interpret)
    return r, o


def _rrms2d_fwd(x, y, w, eps, rows, interpret):
    r, o, rstd = fused_residual_rms_norm_fwd(
        x, y, w, eps=eps, rows=rows, interpret=interpret)
    return (r, o), (r, w, rstd)


def _rrms2d_bwd(eps, rows, interpret, saved, ct):
    r, w, rstd = saved
    dres, dnormed = ct
    d, dw = fused_residual_rms_norm_bwd(
        r, w, rstd, dres, dnormed, eps=eps, rows=rows,
        interpret=interpret)
    return d, d, dw


_rrms2d.defvjp(_rrms2d_fwd, _rrms2d_bwd)


def fused_residual_layer_norm(x, y, weight, bias, *, eps=1e-5,
                              rows=None, interpret=None):
    """Fused residual+LayerNorm over the last axis: x, y [..., h] ->
    (res, normed) with res = x + y (the blocks' residual-stream value)
    and normed = LN(res) * weight + bias.  Differentiable (custom_vjp,
    fused backward kernel)."""
    shape = x.shape
    r, o = _rln2d(x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]),
                  weight, bias, float(eps),
                  None if rows is None else int(rows),
                  _resolve_interpret(interpret))
    return r.reshape(shape), o.reshape(shape)


def fused_residual_layer_norm_twin(x, y, weight, bias, *, eps=1e-5,
                                   rows=None):
    shape = x.shape
    r, o, _, _ = fused_residual_layer_norm_fwd_twin(
        x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]), weight,
        bias, eps=float(eps), rows=rows)
    return r.reshape(shape), o.reshape(shape)


def fused_residual_rms_norm(x, y, weight, *, eps=1e-6, rows=None,
                            interpret=None):
    """Fused residual+RMSNorm over the last axis: (res, normed)."""
    shape = x.shape
    r, o = _rrms2d(x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]),
                   weight, float(eps),
                   None if rows is None else int(rows),
                   _resolve_interpret(interpret))
    return r.reshape(shape), o.reshape(shape)


def fused_residual_rms_norm_twin(x, y, weight, *, eps=1e-6, rows=None):
    shape = x.shape
    r, o, _ = fused_residual_rms_norm_fwd_twin(
        x.reshape(-1, shape[-1]), y.reshape(-1, shape[-1]), weight,
        eps=float(eps), rows=rows)
    return r.reshape(shape), o.reshape(shape)


# --------------------------------------------------------------------------
# autotune entry: fused_residual_norm_rows
# --------------------------------------------------------------------------
def _row_candidates(rows, hidden):
    """Power-of-two row blocks VMEM-capped on the live tiles (x, y, res,
    normed + the fp32 shadows: ~6 f32 row tiles of width hidden)."""
    cap = 12 * 2 ** 20  # conservative VMEM budget
    cands = []
    for c in (64, 128, 256, 512, 1024):
        if c > max(rows, 64):
            break
        if 6 * c * hidden * 4 > cap:
            break
        cands.append(c)
    return cands or [default_rows(rows)]


def pick_glue_rows(rows, hidden):
    """Row block for the glue kernels through the autotune cache (entry
    ``fused_residual_norm_rows``); sweeps fwd+bwd of the LN variant on
    the real [rows, hidden] geometry (pick_mlp_rows discipline)."""
    import numpy as np

    from . import autotune as at
    cands = _row_candidates(rows, hidden)
    fallback = default_rows(rows)
    if len(cands) <= 1:
        return fallback
    sig = f"r{rows}_h{hidden}"
    try:
        cached = at._load_cache().get(
            f"{at._device_kind()}|fused_residual_norm_rows|{sig}")
    except Exception:
        cached = None
    if cached is not None and cached in cands:
        return int(cached)
    if not at.enabled():
        return fallback

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(rows, hidden)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(rows, hidden)), jnp.float32)
    w = jnp.ones((hidden,), jnp.float32)
    b = jnp.zeros((hidden,), jnp.float32)

    def run(cand):
        def fwd_bwd(xv, yv, wv, bv):
            r, o = _rln2d(xv, yv, wv, bv, 1e-5, int(cand), False)
            return jnp.sum(r * r) + jnp.sum(o * o)

        out = jax.grad(fwd_bwd, argnums=(0, 1, 2, 3))(x, y, w, b)
        jax.block_until_ready(out)

    try:
        return int(at.autotune("fused_residual_norm_rows", sig, cands,
                               run))
    except Exception:
        return fallback
