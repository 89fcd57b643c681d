"""Chunked gated delta-rule linear attention with a per-channel decay
(KDA, Kimi Delta Attention), forward and backward.

Per head, with a state ``S`` [dk, dv]::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``a_t = exp(g_t)`` in (0, 1] per CHANNEL of the keys, ``b_t`` in (0, 1)
per head.  Token by token that is one dependent step a position; here a
row is cut into chunks of ``C`` positions and only the state crosses a
chunk's edge.  With ``G`` the decay's running sum inside the chunk
(``G_i = g_1 + .. + g_i``), ``S0`` the state the chunk starts from::

    N_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)      (j <  i)
    M_ij =     sum_c q_ic k_jc exp(G_ic - G_jc)      (j <= i)
    U    = (I + N)^-1 (b v - (b k e^G) S0)           the corrected values
    O    = (q e^G) S0 + M U
    S_C  = diag(e^{G_C}) S0 + (k e^{G_C - G})^T U

(``S_i = diag(e^{G_i}) S0 + sum_{j<=i} diag(e^{G_i - G_j}) k_j u_j^T``
with ``u_i = b_i (v_i - (diag(a_i) S_{i-1})^T k_i)``, written for the
whole chunk at once.)

**The decay is per channel**, so ``exp(G_i - G_j)`` sits inside the sum
over channels and cannot be pulled out of a product of ``q e^G`` and
``k e^-G``: with a strong decay ``e^-G`` overflows inside one chunk.
The scores are therefore made by sub-blocks of ``SUB`` = 16 rows: a
sub-block's scores against the rows BEFORE it are one product of
``q_i exp(G_i - r)`` and ``k_j exp(r - G_j)`` about the reference point
``r`` = ``G`` at the sub-block's edge, both exponents at most 0; its
scores against its own rows are made a column at a time with
``exp(G_i - G_j)`` itself.  Nothing is ever raised to a positive power.

``(I + N)^-1`` (``N`` strictly lower triangular) is exact and made of
products: the sub-blocks' own inverses by ``(I - N)(I + N^2)(I + N^4)
(I + N^8)`` (``N^16 = 0``), the sub-blocks joined by the same finite
series over the block-strictly-lower rest.  These products, the
running sums and the carried state are float32 (``Precision.HIGHEST``
in the kernel); every other product takes its operands in the tensors'
dtype and accumulates in float32.

**One algebra, two executions.**  ``_chunk_fwd`` / ``_chunk_bwd`` are
the chunk's body on plain 2-D values.  On a TPU they are the bodies of
the Pallas kernels ``kda_chunk_fwd`` / ``kda_chunk_bwd``: grid (batch,
head, chunk), the chunk axis sequential, the state (transposed,
[dv, dk], so the decay runs along lanes) carried in VMEM, the operands
read in the caller's [B, S, H * d] layout with no transpose.
Elsewhere ``jax.lax.scan`` over the chunks runs the same bodies under
``vmap`` (the CPU path and the tests' second witness).

**The decay's running sum is the chunk body's own**: the core takes
the decay's logarithm ``g`` as the model hands it over, and both
bodies begin with ``G = _running_sum(g)`` on the [C, dk] float32 tile
they hold: log2(C) adds of the rows shifted by 1, 2, 4, ....  The
backward body ends with the transposed sum of its ``dG`` (``dg_i =
sum_{j >= i} dG_j``; the chunk's last row of ``G`` also decays the
state, and that term goes through the same sum) and returns ``dg``.
Nothing scans over positions outside the kernels: XLA makes a
``cumsum`` over a reshaped axis a window scan over the whole decay,
134 MB a layer at the shape below, in the forward, again in the
layer's recompute and transposed in the backward.

**The backward** is a ``jax.custom_vjp``: a sweep over the chunks in
reverse that carries ``dS``.  The forward SAVES the state each chunk
starts from ([B, H, S / C, dv, dk] float32: 268 MB a layer at 1 x 32 x
8192 x 128 x 128 and chunks of 64, 134 MB at 128) and the backward
recomputes the chunk's running sum, scores, inverse and corrected
values from q, k, v, g and that state: recomputing the states instead
is a second whole forward sweep, a third of the operator's work, for
memory a step has to spare.

On a TPU v5e at [1, 8192, 32, 128] bfloat16 with a float32 decay, the
operator with its glue as it then was (reductions over the last of four
axes), forward / forward + backward: chunk 32 14.04 / 36.58 ms, 64
11.38 / 31.51, 128 9.68 / 27.12 (2026-10-01, PERF.md PR 38); with the
glue in the flat layout (below), the operator at chunk 128 with the
layer's decay gate and gated norm, forward + recompute + backward under
the cell's recompute policy: 43.6 -> 30.1 ms (2026-10-03, PERF.md
PR 43, read while a recompute still kept the spreads; the kernels are
19.6 of either).  The shifted adds are 0.08 ms of the forward kernel's 7.77 at
chunk 128 and 0.10 of the backward's 11.91, where XLA's two window
scans take 1.31 ms a layer; as one product with the triangle of ones,
the decay cut into three bfloat16 parts, the same sums cost the
kernels 0.35 + 0.74 ms, at ``HIGHEST`` 0.46 + 1.07.  The body is
bound by its column-at-a-time score loops and its chain of small
float32 products, not by the MXU or the memory.

**The glue round the core stays in the kernels' layout.**  The L2
normalisation of q and k, the scale and the folding of ``b`` into k and
v (``_fold``), and the layer's gated output norm (``gated_head_norm``),
are jnp on [B, S, H * d] as the projections write it and the kernels
read it (8 positions to a tile).  A sum over a head's ``d`` channels is
a product with the 0/1 indicator ``E`` [H * d, H] (``head_sum``), a
per-head value spread back over them a product with its transpose
(``head_spread``), float32 at ``HIGHEST`` (the indicator is exact in
every part of the split).  Reduced over the last of four axes instead,
XLA lays [B, S, H, d] out with the HEADS on the sublanes and pays a
copy of the whole float32 tensor in and a reshape out of every
reduction, forward, recompute and backward (PERF.md, PR 38 and PR 43).
Both carry a hand-written backward whose residuals are the operands
and the [B, S, H] statistics: a spread left to autodiff is a 134 MB
product that a recompute policy which keeps products saves.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUB = 16                    # rows of a sub-block of the scores
CHUNKS = (16, 32, 64, 128)  # SUB x a power of two; 16 and 32 for short rows
CHUNK = 128                 # the default: the fastest on a v5e (above)
L2_EPS = 1e-6               # x * rsqrt(sum x^2 + eps)

_F32 = jnp.float32
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _mm(a, b, dims, cd):
    """A product with operands in ``cd`` and a float32 result."""
    prec = lax.Precision.HIGHEST if cd == _F32 else None
    return lax.dot_general(a.astype(cd), b.astype(cd), (dims, ((), ())),
                           precision=prec, preferred_element_type=_F32)


def _mm32(a, b, dims=_NN):
    return _mm(a, b, dims, _F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _running_sum(x, reverse=False):
    """``x_1 + .. + x_i`` down the rows of a float32 [C, d] (``reverse``:
    ``x_i + .. + x_C``, its transpose): log2(C) adds of the rows shifted
    by 1, 2, 4, ..., float32 throughout.  On a v5e these sublane shifts
    cost a chunk body a fifth of what one product with the triangle of
    ones does (the head of this file)."""
    C, d = x.shape
    shift = 1
    while shift < C:
        zeros = jnp.zeros((shift, d), x.dtype)
        x = x + (jnp.concatenate([x[shift:], zeros], axis=0) if reverse
                 else jnp.concatenate([zeros, x[:C - shift]], axis=0))
        shift *= 2
    return x


# --------------------------------------------------------------------------
# the chunk's scores, and their backward
# --------------------------------------------------------------------------
def _blocks(x):
    """[C, d] -> [C / SUB, SUB, d]: the sub-blocks side by side."""
    return x.reshape(x.shape[0] // SUB, SUB, x.shape[1])


def _scores(q, k, kb, G, cd):
    """``M`` (j <= i) and ``N`` (j < i), both [C, C] float32, from
    float32 q, k, kb = b k [C, dk] and the running sum G."""
    C = q.shape[0]
    nb = C // SUB
    # a sub-block against the rows before it: one product about the
    # reference point at its edge
    col = _iota((SUB, C), 1)
    off_m, off_n = [jnp.zeros((SUB, C), _F32)], [jnp.zeros((SUB, C), _F32)]
    for lo in range(SUB, C, SUB):
        r = G[lo - 1:lo]
        down = jnp.exp(G[lo:lo + SUB] - r)
        left = jnp.concatenate([q[lo:lo + SUB] * down,
                                kb[lo:lo + SUB] * down], axis=0)
        p = _mm(left, k * jnp.exp(jnp.minimum(r - G, 0.0)), _NT, cd)
        off_m.append(jnp.where(col < lo, p[:SUB], 0.0))
        off_n.append(jnp.where(col < lo, p[SUB:], 0.0))
    # every sub-block against its own rows, a column at a time, all the
    # sub-blocks at once: column j of block b is column b * SUB + j
    q3, k3, kb3, g3 = _blocks(q), _blocks(k), _blocks(kb), _blocks(G)
    at = _iota((nb, SUB, C), 2) - SUB * _iota((nb, SUB, C), 0)
    own_m = own_n = jnp.zeros((nb, SUB, C), _F32)
    for j in range(SUB):
        t = k3[:, j:j + 1] * jnp.exp(jnp.minimum(g3 - g3[:, j:j + 1], 0.0))
        own_m = jnp.where(at == j, jnp.sum(q3 * t, axis=2, keepdims=True),
                          own_m)
        own_n = jnp.where(at == j, jnp.sum(kb3 * t, axis=2, keepdims=True),
                          own_n)
    m = jnp.concatenate(off_m, axis=0) + own_m.reshape(C, C)
    n = jnp.concatenate(off_n, axis=0) + own_n.reshape(C, C)
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    return jnp.where(row >= col, m, 0.0), jnp.where(row > col, n, 0.0)


def _scores_bwd(q, k, kb, G, dm, dn, cd):
    """(dq, dk, dkb, dG) [C, dk] float32 from the masked gradients of
    ``M`` and ``N``.  The reference points carry no gradient: a score
    does not depend on where its exponent was split."""
    C, d = q.shape
    nb = C // SUB
    col = _iota((SUB, C), 1)
    dk = jnp.zeros_like(k)
    dg = jnp.zeros_like(k)
    zero = jnp.zeros((SUB, d), _F32)
    dq_rows, dkb_rows, dg_rows = [zero], [zero], [zero]
    for lo in range(SUB, C, SUB):
        r = G[lo - 1:lo]
        down = jnp.exp(G[lo:lo + SUB] - r)
        qd, kbd = q[lo:lo + SUB] * down, kb[lo:lo + SUB] * down
        up = jnp.exp(jnp.minimum(r - G, 0.0))
        right = k * up
        d_off = jnp.concatenate(
            [jnp.where(col < lo, dm[lo:lo + SUB], 0.0),
             jnp.where(col < lo, dn[lo:lo + SUB], 0.0)], axis=0)
        d_left = _mm(d_off, right, _NN, cd)
        d_right = _mm(d_off, jnp.concatenate([qd, kbd], axis=0), _TN, cd)
        dqd, dkbd = d_left[:SUB], d_left[SUB:]
        dq_rows.append(dqd * down)
        dkb_rows.append(dkbd * down)
        dg_rows.append(dqd * qd + dkbd * kbd)
        dk = dk + d_right * up
        dg = dg - d_right * right
    cat = functools.partial(jnp.concatenate, axis=0)
    q3, k3, kb3, g3 = _blocks(q), _blocks(k), _blocks(kb), _blocks(G)
    dm3, dn3 = dm.reshape(nb, SUB, C), dn.reshape(nb, SUB, C)
    at = _iota((nb, SUB, C), 2) - SUB * _iota((nb, SUB, C), 0)
    row = _iota((1, SUB, 1), 1)
    dq3 = dkb3 = dk3 = dg3 = jnp.zeros_like(q3)
    for j in range(SUB):
        e = jnp.exp(jnp.minimum(g3 - g3[:, j:j + 1], 0.0))
        t = k3[:, j:j + 1] * e
        dmc = jnp.sum(jnp.where(at == j, dm3, 0.0), axis=2, keepdims=True)
        dnc = jnp.sum(jnp.where(at == j, dn3, 0.0), axis=2, keepdims=True)
        dq3 = dq3 + dmc * t
        dkb3 = dkb3 + dnc * t
        dt = dmc * q3 + dnc * kb3
        de = dt * t
        dk3 = dk3 + jnp.where(
            row == j, jnp.sum(dt * e, axis=1, keepdims=True), 0.0)
        dg3 = dg3 + de - jnp.where(
            row == j, jnp.sum(de, axis=1, keepdims=True), 0.0)
    return (cat(dq_rows) + dq3.reshape(C, d), dk + dk3.reshape(C, d),
            cat(dkb_rows) + dkb3.reshape(C, d),
            dg + cat(dg_rows) + dg3.reshape(C, d))


def _solve(n):
    """``(I + n)^-1`` of a strictly lower triangular [C, C], float32."""
    C = n.shape[0]
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    eye = (row == col).astype(_F32)
    own = (row // SUB) == (col // SUB)
    nd = jnp.where(own, n, 0.0)
    d = eye - nd                        # the sub-blocks' own inverses
    p = _mm32(nd, nd)
    for step in range(3):               # SUB = 16: n^16 = 0
        d = d + _mm32(d, p)
        if step < 2:
            p = _mm32(p, p)
    blocks = C // SUB
    if blocks == 1:
        return d
    b = _mm32(d, n - nd)                # block-strictly lower: b^blocks = 0
    y = eye - b
    p = _mm32(b, b)
    power = 2
    while power < blocks:
        y = y + _mm32(y, p)
        power *= 2
        if power < blocks:
            p = _mm32(p, p)
    return _mm32(y, d)


# --------------------------------------------------------------------------
# the chunk's body
# --------------------------------------------------------------------------
def _chunk_fwd(q, k, kb, vb, g, st, cd):
    """One chunk: q (scaled), k, kb = b k [C, dk], vb = b v [C, dv],
    the decay's logarithm g [C, dk] float32, ``st`` the state it starts
    from, TRANSPOSED [dv, dk] float32 -> (o [C, dv] float32, the state
    it leaves)."""
    C = q.shape[0]
    q, k, kb, vb = (x.astype(_F32) for x in (q, k, kb, vb))
    G = _running_sum(g)
    m, n = _scores(q, k, kb, G, cd)
    x = _solve(n)
    e = jnp.exp(G)
    u = _mm(x, vb - _mm(kb * e, st, _NT, cd), _NN, cd)
    o = _mm(q * e, st, _NT, cd) + _mm(m, u, _NN, cd)
    gc = G[C - 1:C]
    st = st * jnp.exp(gc) + _mm(u, k * jnp.exp(gc - G), _TN, cd)
    return o, st


def _chunk_bwd(q, k, kb, vb, g, st, do, dst, cd):
    """The chunk's backward from its inputs, the state it started from,
    ``do`` [C, dv] and the gradient ``dst`` [dv, dk] of the state it
    left -> (dq, dk, dkb [C, dk], dvb [C, dv], dg [C, dk], the gradient
    of the state it started from), all float32."""
    C = q.shape[0]
    q, k, kb, vb, do = (x.astype(_F32) for x in (q, k, kb, vb, do))
    G = _running_sum(g)
    row, col = _iota((C, C), 0), _iota((C, C), 1)
    m, n = _scores(q, k, kb, G, cd)
    x = _solve(n)
    e = jnp.exp(G)
    gc = G[C - 1:C]
    ec, el = jnp.exp(gc), jnp.exp(gc - G)
    qg, kgb, kbar = q * e, kb * e, k * el
    r = vb - _mm(kgb, st, _NT, cd)
    u = _mm(x, r, _NN, cd)

    dqg = _mm(do, st, _NN, cd)
    dst0 = _mm(do, qg, _TN, cd) + dst * ec
    dm = jnp.where(row >= col, _mm(do, u, _NT, cd), 0.0)
    du = _mm(m, do, _TN, cd) + _mm(kbar, dst, _NT, cd)
    dkbar = _mm(u, dst, _NN, cd)
    dx = _mm(du, r, _NT, cd)
    dr = _mm(x, du, _TN, cd)
    dkgb = -_mm(dr, st, _NN, cd)
    dst0 = dst0 - _mm(dr, kgb, _TN, cd)
    dn = jnp.where(row > col, -_mm32(_mm32(x, dx, _TN), x, _NT), 0.0)

    dq, dk, dkb, dG = _scores_bwd(q, k, kb, G, dm, dn, cd)
    t = dkbar * kbar
    dq = dq + dqg * e
    dkb = dkb + dkgb * e
    dk = dk + dkbar * el
    # the chunk's last row of G also decays the state and the keys
    last = (jnp.sum(t, axis=0, keepdims=True)
            + jnp.sum(dst * st, axis=0, keepdims=True) * ec)
    dG = dG + dqg * qg + dkgb * kgb - t \
        + jnp.where(_iota((C, 1), 0) == C - 1, last, 0.0)
    # g_i is in every G_j from row i on
    return dq, dk, dkb, dr, _running_sum(dG, reverse=True), dst0


# --------------------------------------------------------------------------
# the XLA form: the same bodies under scan and vmap
# --------------------------------------------------------------------------
def _by_chunk(x, chunk):
    """[B, S, H, d] -> [B, H, S / chunk, chunk, d]."""
    b, s, h, d = x.shape
    return x.reshape(b, s // chunk, chunk, h, d).transpose(0, 3, 1, 2, 4)


def _by_row(x):
    """[B, H, N, C, d] -> [B, N * C, H, d]."""
    b, h, n, c, d = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h, d)


@functools.partial(jax.jit, static_argnames="chunk")
def _fwd_xla(q, k, kb, vb, g, chunk):
    cd = q.dtype
    dk, dv = q.shape[-1], vb.shape[-1]

    def head(q, k, kb, vb, g):
        def step(st, xs):
            o, new = _chunk_fwd(*xs, st, cd)
            return new, (o, st)
        _, (o, states) = lax.scan(step, jnp.zeros((dv, dk), _F32),
                                  (q, k, kb, vb, g))
        return o, states

    o, states = jax.vmap(jax.vmap(head))(
        *(_by_chunk(x, chunk) for x in (q, k, kb, vb, g)))
    return _by_row(o).astype(cd), states


@functools.partial(jax.jit, static_argnames="chunk")
def _bwd_xla(q, k, kb, vb, g, states, do, chunk):
    cd = q.dtype
    dk, dv = q.shape[-1], vb.shape[-1]

    def head(q, k, kb, vb, g, states, do):
        def step(dst, xs):
            *grads, dst = _chunk_bwd(*xs, dst, cd)
            return dst, tuple(grads)
        _, grads = lax.scan(step, jnp.zeros((dv, dk), _F32),
                            (q, k, kb, vb, g, states, do), reverse=True)
        return grads

    grads = jax.vmap(jax.vmap(head))(
        *(_by_chunk(x, chunk) for x in (q, k, kb, vb, g)), states,
        _by_chunk(do, chunk))
    return tuple(_by_row(x) for x in grads)


# --------------------------------------------------------------------------
# the kernels
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, o_ref, s_ref, st):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st[...] = jnp.zeros_like(st)

    start = st[...]
    s_ref[...] = start
    o, new = _chunk_fwd(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...],
                        g_ref[...], start, q_ref.dtype)
    o_ref[...] = o.astype(o_ref.dtype)
    st[...] = new


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s_ref, do_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dg_ref, dst):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst[...] = jnp.zeros_like(dst)

    dq, dk, dkb, dvb, dg, new = _chunk_bwd(
        q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], g_ref[...],
        s_ref[...], do_ref[...], dst[...], q_ref.dtype)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dkb_ref[...] = dkb.astype(dkb_ref.dtype)
    dvb_ref[...] = dvb.astype(dvb_ref.dtype)
    dg_ref[...] = dg
    dst[...] = new


def _flat(x):
    """[B, S, H, d] -> [B, S, H * d]: a head's chunk is then a block."""
    return x.reshape(*x.shape[:2], -1)


def _publish_chunks(kernel, shape, dv, chunk):
    """Gauge ``kda.chunks{kernel, shape}``: the chunk bodies one call of
    this shape runs (batch x heads x chunks a row), set while the call
    is traced."""
    from ...observability import metrics
    b, s, h, dk = shape
    metrics.registry().gauge(
        "kda.chunks", "chunk bodies a KDA call runs",
        labels={"kernel": kernel,
                "shape": f"b{b}h{h}s{s}dk{dk}dv{dv}c{chunk}"}
    ).set(b * h * (s // chunk))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _fwd_pallas(q, k, kb, vb, g, chunk, interpret):
    b, s, h, dk = q.shape
    dv, n = vb.shape[-1], s // chunk
    _publish_chunks("fwd", q.shape, dv, chunk)

    def rows(d):
        return pl.BlockSpec((None, chunk, d), lambda ib, ih, ic: (ib, ic, ih))

    o, states = pl.pallas_call(
        _fwd_kernel,
        grid=(b, h, n),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk)],
        out_specs=[rows(dv),
                   pl.BlockSpec((None, None, None, dv, dk),
                                lambda ib, ih, ic: (ib, ih, ic, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, n, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(*(_flat(x) for x in (q, k, kb, vb, g)))
    return o.reshape(b, s, h, dv), states


def _bwd_pallas(q, k, kb, vb, g, states, do, chunk, interpret):
    b, s, h, dk = q.shape
    dv, n = vb.shape[-1], s // chunk
    _publish_chunks("bwd", q.shape, dv, chunk)

    def rows(d):        # the chunks from the last to the first
        return pl.BlockSpec((None, chunk, d),
                            lambda ib, ih, ic: (ib, n - 1 - ic, ih))

    grads = pl.pallas_call(
        _bwd_kernel,
        grid=(b, h, n),
        in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk),
                  pl.BlockSpec((None, None, None, dv, dk),
                               lambda ib, ih, ic: (ib, ih, n - 1 - ic, 0, 0)),
                  rows(dv)],
        out_specs=[rows(dk), rows(dk), rows(dk), rows(dv), rows(dk)],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dk), q.dtype)] * 3
        + [jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
           jax.ShapeDtypeStruct((b, s, h * dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(*(_flat(x) for x in (q, k, kb, vb, g)), states, _flat(do))
    return tuple(x.reshape(b, s, h, -1) for x in grads)


# --------------------------------------------------------------------------
# the core and its gradient
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _core(q, k, kb, vb, g, chunk, how):
    return _core_fwd(q, k, kb, vb, g, chunk, how)[0]


def _core_fwd(q, k, kb, vb, g, chunk, how):
    if how == "xla":
        o, states = _fwd_xla(q, k, kb, vb, g, chunk=chunk)
    else:
        o, states = _fwd_pallas(q, k, kb, vb, g, chunk, how == "interpret")
    return o, (q, k, kb, vb, g, states)


def _core_bwd(chunk, how, res, do):
    q = res[0]
    if how == "xla":
        dq, dk, dkb, dvb, dg = _bwd_xla(*res, do, chunk=chunk)
    else:
        dq, dk, dkb, dvb, dg = _bwd_pallas(*res, do, chunk,
                                           how == "interpret")
    return (dq.astype(q.dtype), dk.astype(q.dtype), dkb.astype(q.dtype),
            dvb.astype(res[3].dtype), dg.astype(_F32))


_core.defvjp(_core_fwd, _core_bwd)


# --------------------------------------------------------------------------
# the glue round the core, in the kernels' own [B, S, H * d] layout
# --------------------------------------------------------------------------
def _head_of(width, heads):
    """``E`` [H * d, H] float32, 1 where a channel is its head's: a sum
    over a head's channels is a product with it, a per-head value spread
    over them a product with its transpose."""
    return (_iota((width, heads), 0) // (width // heads)
            == _iota((width, heads), 1)).astype(_F32)


def head_sum(x, heads):
    """[B, S, H * d] float32 -> [B, S, H]: each head's sum over its ``d``
    channels, float32 (``HIGHEST``: the indicator is exact in any part
    of a split), with the positions left on the sublanes."""
    return lax.dot_general(
        x, _head_of(x.shape[-1], heads), (((2,), (0,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=_F32)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def head_spread(r, width):
    """[B, S, H] float32 -> [B, S, H * d]: a head's value on each of its
    channels, exactly.

    A ``custom_jvp`` (it is linear: its tangent is itself) so that
    inside the hand-written rules below, which nothing differentiates,
    it stays ONE operation under its own name.  Left a bare
    ``dot_general``, a recompute policy that keeps products keeps this
    one wherever a value made from it is wanted again in the backward
    (the core's operands, the gated norm's result): 128 copies of what
    [B, S, H] says, four 134 MB float32 residuals a layer at the cell's
    shape (PERF.md, PR 43)."""
    return lax.dot_general(
        r, _head_of(width, r.shape[-1]), (((2,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST, preferred_element_type=_F32)


@head_spread.defjvp
def _head_spread_jvp(width, primals, tangents):
    return head_spread(*primals, width), head_spread(*tangents, width)


def _l2_bwd(dxn, xn, r, heads):
    """The gradient of ``x`` through ``xn = x * r``, ``r = rsqrt(sum_head
    x^2 + eps)`` spread over the head, from that of ``xn``."""
    return r * (dxn - xn * head_spread(head_sum(dxn * xn, heads),
                                       xn.shape[-1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fold(q, k, v, beta, scale, cd):
    """The core's operands from the caller's, all [B, S, H * d] (``beta``
    [B, S, H]): q and k L2-normalised per head, q times ``scale``,
    ``beta`` folded into k and v; float32 inside, the results in ``cd``.

    Its own ``custom_vjp`` so that what a backward keeps is q, k, v,
    beta and the two [B, S, H] norms: left to autodiff, each spread (a
    product) is a 134 MB float32 residual that a recompute policy which
    keeps products would save."""
    return _fold_fwd(q, k, v, beta, scale, cd)[0]


def _beta_spread(beta, k, v):
    """``beta`` on k's channels and on v's (one product where the two
    widths are one)."""
    b = beta.astype(_F32)
    bk = head_spread(b, k.shape[-1])
    return bk, bk if v.shape[-1] == k.shape[-1] else head_spread(
        b, v.shape[-1])


def _fold_fwd(q, k, v, beta, scale, cd):
    heads, width = beta.shape[-1], q.shape[-1]
    q32, k32 = q.astype(_F32), k.astype(_F32)
    rq = lax.rsqrt(head_sum(q32 * q32, heads) + L2_EPS)
    rk = lax.rsqrt(head_sum(k32 * k32, heads) + L2_EPS)
    kn = k32 * head_spread(rk, width)
    bk, bv = _beta_spread(beta, k, v)
    out = ((q32 * head_spread(rq, width) * scale).astype(cd), kn.astype(cd),
           (kn * bk).astype(cd), (v.astype(_F32) * bv).astype(cd))
    return out, (q, k, v, beta, rq, rk)


def _fold_bwd(scale, cd, res, grads):
    q, k, v, beta, rq, rk = res
    heads, width = beta.shape[-1], q.shape[-1]
    dqn, dkn, dkb, dvb = (x.astype(_F32) for x in grads)
    rq, rk = head_spread(rq, width), head_spread(rk, width)
    bk, bv = _beta_spread(beta, k, v)
    kn = k.astype(_F32) * rk
    by_k, by_v = dkb * kn, dvb * v.astype(_F32)
    dbeta = (head_sum(by_k + by_v, heads) if bk is bv
             else head_sum(by_k, heads) + head_sum(by_v, heads))
    dq = _l2_bwd(dqn * scale, q.astype(_F32) * rq, rq, heads)
    dk = _l2_bwd(dkn + dkb * bk, kn, rk, heads)
    return (dq.astype(q.dtype), dk.astype(k.dtype),
            (dvb * bv).astype(v.dtype), dbeta.astype(beta.dtype))


_fold.defvjp(_fold_fwd, _fold_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_head_norm(o, weight, gate, heads, eps):
    """Per head ``RMSNorm(o) * weight * sigmoid(gate)``, the KDA layer's
    output norm: ``o`` and ``gate`` [B, S, H * d], ``weight`` [d];
    float32 inside, the result in o's dtype.  A ``custom_vjp`` for
    ``_fold``'s reason: the backward keeps o, the gate and the [B, S,
    H] ``rsqrt``, not its spread."""
    return _gated_head_norm_fwd(o, weight, gate, heads, eps)[0]


def _gated_head_norm_fwd(o, weight, gate, heads, eps):
    x = o.astype(_F32)
    r = lax.rsqrt(head_sum(x * x, heads) / weight.shape[0] + eps)
    y = (x * head_spread(r, x.shape[-1])
         * jnp.tile(weight.astype(_F32), heads)
         * jax.nn.sigmoid(gate.astype(_F32)))
    return y.astype(o.dtype), (o, weight, gate, r)


def _gated_head_norm_bwd(heads, eps, res, dy):
    o, weight, gate, r = res
    d = weight.shape[0]
    r = head_spread(r, o.shape[-1])
    xn = o.astype(_F32) * r
    s = jax.nn.sigmoid(gate.astype(_F32))
    w = jnp.tile(weight.astype(_F32), heads)
    dy = dy.astype(_F32)
    t = dy * xn
    dxn = dy * w * s
    dx = r * (dxn - xn * head_spread(head_sum(dxn * xn, heads) / d,
                                     xn.shape[-1]))
    dw = jnp.sum(t * s, axis=(0, 1)).reshape(heads, d).sum(0)
    return (dx.astype(o.dtype), dw.astype(weight.dtype),
            (t * w * s * (1.0 - s)).astype(gate.dtype))


gated_head_norm.defvjp(_gated_head_norm_fwd, _gated_head_norm_bwd)


def kda_chunk(q, k, v, g, beta, *, chunk=None, scale=None, how=None):
    """``o`` [B, S, H, dv] of the recurrence in this file's head.

    ``q``, ``k`` [B, S, H, dk] (L2-normalised here, per head; ``q``
    then times ``scale``, default ``1 / sqrt(dk)``), ``v`` [B, S, H,
    dv], ``g`` [B, S, H, dk] the decay's logarithm, at most 0, ``beta``
    [B, S, H] in (0, 1).  ``o`` has q's dtype; the products take their
    operands in it.  ``chunk`` is one of ``CHUNKS`` (None: ``CHUNK``); a
    row that is not
    a multiple of it is padded with positions that leave the state as
    it is.  ``how``: None runs the kernels on a TPU and the XLA form
    elsewhere; ``"xla"``, ``"pallas"`` and ``"interpret"`` are the
    tests'."""
    if chunk is None:
        chunk = CHUNK
    if chunk not in CHUNKS:
        raise ValueError(f"kda_chunk: chunk {chunk} is not one of {CHUNKS}")
    if how is None:
        how = "pallas" if jax.default_backend() == "tpu" else "xla"
    if how not in ("xla", "pallas", "interpret"):
        raise ValueError(f"kda_chunk: how={how!r}")
    b, s, h, dk = q.shape
    if k.shape != q.shape or g.shape != q.shape:
        raise ValueError(
            f"kda_chunk: k {tuple(k.shape)} and g {tuple(g.shape)} must "
            f"have q's shape {tuple(q.shape)}: the decay is per channel "
            f"of the keys")
    if v.shape[:3] != (b, s, h) or beta.shape != (b, s, h):
        raise ValueError(
            f"kda_chunk: v {tuple(v.shape)} and beta {tuple(beta.shape)} "
            f"must match q {tuple(q.shape)} in batch, positions and heads")
    if scale is None:
        scale = 1.0 / math.sqrt(dk)
    pad = -s % chunk

    def by_head(x):
        # k = 0 and g = 0: the state passes a padded position unchanged
        # (the running sum of 0 is flat).  Four dimensions again for the
        # core, which flattens them: the two reshapes cancel
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        return x.reshape(b, s + pad, h, -1)

    folded = _fold(_flat(q), _flat(k), _flat(v), beta, scale, q.dtype)
    return _core(*(by_head(x) for x in folded),
                 by_head(_flat(g).astype(_F32)), chunk, how)[:, :s]
