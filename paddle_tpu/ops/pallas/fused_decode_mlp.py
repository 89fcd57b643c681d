"""Fused decode-egress Pallas kernels (ISSUE 18 tentpole, kernel 2/2):

* ``fused_decode_mlp`` — attention out-projection + residual + MLP
  (fc1/gelu/fc2 for GPT, gate/up/SwiGLU/down for LLaMA) + second
  residual in ONE dispatch per decode layer;
* ``fused_decode_mlp_partial`` — the tensor-parallel shard-local
  partial of the same chain: norm -> fc1(+act) -> @w2_local, returned
  PRE-psum so the TP decode bodies keep their psum-per-layer contract
  (psum + bias + residual stay outside, exactly where the unfused body
  puts them);
* ``fused_decode_epilogue`` — the final-norm + lm_head + guarded
  greedy argmax sampling step riding the last layer's output tile,
  replaying ``generation.guarded_argmax``'s poison/finiteness math so
  the engine's freeze rule sees bit-identical (next-token, bad) pairs.

Same discipline as fused_decode_qkv: the block math replays the EXACT
unfused op order (functional jnp norms, ``jnp.matmul`` projections,
``jax.nn.gelu(approximate=True)`` / ``jax.nn.silu`` activations,
residual operand order), each kernel has an unjitted jnp twin walking
identical row blocks for BITWISE interpret parity, and the row block is
an autotune entry (``fused_decode_mlp_rows`` — ``pick_mlp_rows``).

Weights are VMEM-resident per block (decode-sized hidden/vocab widths;
the candidates in ``pick_mlp_rows`` are VMEM-capped like the qkv
kernel's).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import out_struct

from .fused_decode_qkv import _mm, _norm_block, _row_candidates, \
    default_rows


def _mlp_tail(h, w1, b1, w2, b2, wu, arch):
    """fc1 -> activation -> fc2 (GPT) or gate/up -> SwiGLU -> down
    (LLaMA), matching GPTMLP/LlamaMLP op order."""
    if arch == "gpt":
        f = _mm(h, w1)
        if b1 is not None:
            f = f + b1
        f = jax.nn.gelu(f, approximate=True)
    else:
        f = jax.nn.silu(_mm(h, w1)) * _mm(h, wu)
    f2 = _mm(f, w2)
    if b2 is not None:
        f2 = f2 + b2
    return f2


def _mlp_block(xv, av, wo, bo, nw, nb, w1, b1, w2, b2, wu, *, arch,
               norm, eps):
    """One row-block of the fused egress math.  Residual operand order
    matches the decode bodies (``x = x + proj(att)`` then
    ``x = x + mlp(norm(x))``)."""
    prj = _mm(av, wo)
    if bo is not None:
        prj = prj + bo
    y1 = xv + prj
    h = _norm_block(y1, nw, nb, norm, eps)
    return y1 + _mlp_tail(h, w1, b1, w2, b2, wu, arch)


def _mlp_partial_block(yv, nw, nb, w1, b1, w2, wu, *, arch, norm, eps):
    """Shard-local TP partial: norm -> fc1(+act) -> @w2_local, before
    the layer's psum (the TP body adds psum + fc2 bias + residual)."""
    h = _norm_block(yv, nw, nb, norm, eps)
    return _mlp_tail(h, w1, b1, w2, None, wu, arch)


def _epilogue_block(xv, nw, nb, wlm, blm, poisonv, *, norm, eps,
                    transpose_lm):
    """Final norm + lm_head + generation.guarded_argmax math.  Returns
    (logits [rows, V] pre-poison — what the unfused step emits —
    nxt [rows] i32, bad [rows] bool)."""
    h = _norm_block(xv, nw, nb, norm, eps)
    if transpose_lm:
        lg0 = _mm(h, jnp.swapaxes(wlm, -1, -2))
    else:
        lg0 = _mm(h, wlm)
        if blm is not None:
            lg0 = lg0 + blm
    lg = lg0.astype(jnp.float32) + poisonv
    bad = ~jnp.all(jnp.isfinite(lg), axis=-1)
    nxt = jnp.where(bad, 0, lg.argmax(axis=-1)).astype(jnp.int32)
    return lg0, nxt, bad


def _pad_rows(x, bp):
    pad = bp - x.shape[0]
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) \
        if pad else x


def _blocked_call(block_fn, row_args, full_args, n_valid, rows,
                  interpret, n_multi=1):
    """Run ``block_fn(*row_blocks, *full_args)`` over row blocks as ONE
    pallas_call (kernel path) — shared by the three egress wrappers.
    ``row_args`` are [B, ...] tensors blocked on rows; ``full_args`` are
    block-invariant (weights, [1, H] params), with None entries elided
    from the call and re-inserted inside the kernel.  Returns outputs
    sliced back to ``n_valid`` rows."""
    rows_c = n_valid if rows is None else int(rows)
    bp = ((n_valid + rows_c - 1) // rows_c) * rows_c
    row_p = [_pad_rows(a, bp) for a in row_args]
    present = [a for a in full_args if a is not None]
    mask = [a is not None for a in full_args]

    abs_outs = jax.eval_shape(
        block_fn,
        *[jax.ShapeDtypeStruct((rows_c,) + a.shape[1:], a.dtype)
          for a in row_p],
        *[None if a is None else
          jax.ShapeDtypeStruct(a.shape, a.dtype) for a in full_args])
    if not isinstance(abs_outs, tuple):
        abs_outs = (abs_outs,)

    def kernel(*refs):
        vals = iter(refs[:len(row_p) + len(present)])
        rvals = [next(vals)[...] for _ in row_p]
        fvals = [next(vals)[...] if m else None for m in mask]
        outs = block_fn(*rvals, *fvals)
        if not isinstance(outs, tuple):
            outs = (outs,)
        for o_ref, o in zip(refs[len(row_p) + len(present):], outs):
            if o.dtype == jnp.bool_:
                o = o.astype(jnp.int32)  # bool pallas outputs are flaky
            o_ref[...] = o.reshape(o_ref.shape)

    def blk(shape):
        ix = lambda i: (i,) + (0,) * (len(shape) - 1)  # noqa: E731
        return pl.BlockSpec((rows_c,) + tuple(shape[1:]), ix)

    def fullspec(shape):
        return pl.BlockSpec(tuple(shape),
                            lambda i, _n=len(shape): (0,) * _n)

    out_shape, out_specs = [], []
    for o in abs_outs:
        dt = jnp.int32 if o.dtype == jnp.bool_ else o.dtype
        shp = (bp,) + o.shape[1:]
        if len(shp) == 1:
            shp = (bp, 1)
        out_shape.append(out_struct(shp, dt, *row_p, *present))
        out_specs.append(blk(shp))

    outs = pl.pallas_call(
        kernel, grid=(bp // rows_c,),
        in_specs=[blk(a.shape) for a in row_p] +
                 [fullspec(a.shape) for a in present],
        out_specs=out_specs, out_shape=out_shape,
        interpret=bool(interpret))(*row_p, *present)
    if not isinstance(outs, (list, tuple)):
        outs = (outs,)
    final = []
    for o, a in zip(outs, abs_outs):
        o = o[:n_valid]
        if len(a.shape) == 1:
            o = o[:, 0]
        if a.dtype == jnp.bool_:
            o = o != 0
        final.append(o)
    return tuple(final)


def _blocked_twin(block_fn, row_args, full_args, n_valid, rows):
    """Twin of ``_blocked_call`` outside any pallas_call: identical
    padding, identical per-block math, concatenated back — bitwise vs
    interpret mode.  The block math runs under ``jax.jit`` so both
    sides share XLA's elementwise-fusion (FMA) semantics (op-by-op
    eager drifts ~1 ulp on scale/shift chains)."""
    rows_c = n_valid if rows is None else int(rows)
    bp = ((n_valid + rows_c - 1) // rows_c) * rows_c
    row_p = [_pad_rows(a, bp) for a in row_args]
    jfn = jax.jit(block_fn)
    blocks = []
    for i in range(bp // rows_c):
        sl = slice(i * rows_c, (i + 1) * rows_c)
        outs = jfn(*[a[sl] for a in row_p], *full_args)
        blocks.append(outs if isinstance(outs, tuple) else (outs,))
    final = []
    for parts in zip(*blocks):
        final.append(jnp.concatenate(parts, axis=0)[:n_valid])
    return tuple(final)


def _resolve_interpret(interpret):
    if interpret is None:
        from . import use_interpret
        return use_interpret()
    return bool(interpret)


def fused_decode_mlp(x, att, wo, bo, norm_w, norm_b, w1, b1, w2, b2,
                     w_up=None, *, arch="gpt", norm="layer", eps=1e-5,
                     rows=None, interpret=None):
    """x [B, H] residual stream, att [B, nh*hd] attention output ->
    [B, H] after out-proj + residual + MLP + residual."""
    fn = functools.partial(_mlp_block, arch=arch, norm=norm, eps=eps)
    nw = norm_w.reshape(1, -1)
    nb = norm_b.reshape(1, -1) if norm_b is not None else None
    full = [wo, None if bo is None else bo.reshape(1, -1), nw, nb,
            w1, None if b1 is None else b1.reshape(1, -1),
            w2, None if b2 is None else b2.reshape(1, -1), w_up]
    return _blocked_call(lambda xv, av, *f: fn(xv, av, *f),
                         [x, att], full, x.shape[0], rows,
                         _resolve_interpret(interpret))[0]


def fused_decode_mlp_twin(x, att, wo, bo, norm_w, norm_b, w1, b1, w2,
                          b2, w_up=None, *, arch="gpt", norm="layer",
                          eps=1e-5, rows=None, interpret=None):
    del interpret
    fn = functools.partial(_mlp_block, arch=arch, norm=norm, eps=eps)
    nw = norm_w.reshape(1, -1)
    nb = norm_b.reshape(1, -1) if norm_b is not None else None
    full = [wo, None if bo is None else bo.reshape(1, -1), nw, nb,
            w1, None if b1 is None else b1.reshape(1, -1),
            w2, None if b2 is None else b2.reshape(1, -1), w_up]
    return _blocked_twin(lambda xv, av, *f: fn(xv, av, *f),
                         [x, att], full, x.shape[0], rows)[0]


def fused_decode_mlp_partial(y1, norm_w, norm_b, w1, b1, w2, w_up=None,
                             *, arch="gpt", norm="layer", eps=1e-5,
                             rows=None, interpret=None):
    """TP shard-local partial: y1 [B, H] (post-attention residual) ->
    pre-psum MLP partial [B, H]."""
    fn = functools.partial(_mlp_partial_block, arch=arch, norm=norm,
                           eps=eps)
    full = [norm_w.reshape(1, -1),
            None if norm_b is None else norm_b.reshape(1, -1),
            w1, None if b1 is None else b1.reshape(1, -1), w2, w_up]
    return _blocked_call(lambda yv, *f: fn(yv, *f), [y1], full,
                         y1.shape[0], rows,
                         _resolve_interpret(interpret))[0]


def fused_decode_mlp_partial_twin(y1, norm_w, norm_b, w1, b1, w2,
                                  w_up=None, *, arch="gpt",
                                  norm="layer", eps=1e-5, rows=None,
                                  interpret=None):
    del interpret
    fn = functools.partial(_mlp_partial_block, arch=arch, norm=norm,
                           eps=eps)
    full = [norm_w.reshape(1, -1),
            None if norm_b is None else norm_b.reshape(1, -1),
            w1, None if b1 is None else b1.reshape(1, -1), w2, w_up]
    return _blocked_twin(lambda yv, *f: fn(yv, *f), [y1], full,
                         y1.shape[0], rows)[0]


def fused_decode_epilogue(x, norm_w, norm_b, w_lm, b_lm, poison, *,
                          norm="layer", eps=1e-5, transpose_lm=False,
                          rows=None, interpret=None):
    """x [B, H] final hidden state, poison [B] f32 (the engine guard's
    per-slot poison lane) -> (logits [B, V], nxt [B] i32, bad [B]
    bool), with nxt/bad exactly ``guarded_argmax``'s outputs.
    ``transpose_lm`` selects the tied-embedding ``matmul(h, wte.T)``
    form (w_lm passed [V, H])."""
    fn = functools.partial(_epilogue_block, norm=norm, eps=eps,
                           transpose_lm=transpose_lm)
    full = [norm_w.reshape(1, -1),
            None if norm_b is None else norm_b.reshape(1, -1),
            w_lm, None if b_lm is None else b_lm.reshape(1, -1)]
    lg, nxt, bad = _blocked_call(
        lambda xv, pv, *f: fn(xv, *f, pv), [x, poison.reshape(-1, 1)],
        full, x.shape[0], rows, _resolve_interpret(interpret))
    return lg, nxt, bad


def fused_decode_epilogue_twin(x, norm_w, norm_b, w_lm, b_lm, poison,
                               *, norm="layer", eps=1e-5,
                               transpose_lm=False, rows=None,
                               interpret=None):
    del interpret
    fn = functools.partial(_epilogue_block, norm=norm, eps=eps,
                          transpose_lm=transpose_lm)
    full = [norm_w.reshape(1, -1),
            None if norm_b is None else norm_b.reshape(1, -1),
            w_lm, None if b_lm is None else b_lm.reshape(1, -1)]
    lg, nxt, bad = _blocked_twin(
        lambda xv, pv, *f: fn(xv, *f, pv), [x, poison.reshape(-1, 1)],
        full, x.shape[0], rows)
    return lg, nxt, bad


# --------------------------------------------------------------------------
# autotune entry: fused_decode_mlp_rows
# --------------------------------------------------------------------------
def pick_mlp_rows(b, hidden, inter):
    """Row block for fused_decode_mlp through the autotune cache
    (entry ``fused_decode_mlp_rows``); candidates VMEM-capped on the
    widest activation tile (the fc1/gate output)."""
    import numpy as np
    from . import autotune as at
    cands = _row_candidates(b, hidden, inter)
    fallback = default_rows(b)
    if len(cands) <= 1:
        return fallback
    sig = f"b{b}_h{hidden}_i{inter}"
    try:
        cached = at._load_cache().get(
            f"{at._device_kind()}|fused_decode_mlp_rows|{sig}")
    except Exception:
        cached = None
    if cached is not None and cached in cands:
        return int(cached)
    if not at.enabled():
        return fallback

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, hidden)), jnp.float32)
    att = jnp.asarray(rng.normal(size=(b, hidden)), jnp.float32)
    wo = jnp.asarray(rng.normal(size=(hidden, hidden)) * 0.02,
                     jnp.float32)
    nw = jnp.ones((hidden,), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(hidden, inter)) * 0.02,
                     jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(inter, hidden)) * 0.02,
                     jnp.float32)

    def run(cand):
        out = fused_decode_mlp(
            x, att, wo, None, nw, None, w1, None, w2, None,
            arch="llama", norm="rms", eps=1e-6, w_up=w1,
            rows=int(cand))
        jax.block_until_ready(out)

    try:
        return int(at.autotune("fused_decode_mlp_rows", sig, cands,
                               run))
    except Exception:
        return fallback
