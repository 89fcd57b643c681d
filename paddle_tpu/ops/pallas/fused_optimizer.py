"""Fused multi-tensor optimizer update kernel (SGD / Momentum / Adam /
AdamW) over flat dtype-bucketed state.

Capability analog of the reference's fused optimizer CUDA tier
(``paddle/phi/kernels/fused_adam_kernel.cu``, the ``multi_tensor_apply``
family): one kernel applies gradient clip scale + regularizer fold +
moment updates + weight decay + master-weight cast in a single pass over
a flat bucket (``optimizer/flat.py``), instead of O(num_params) little
elementwise chains.

Two interchangeable implementations with identical arithmetic:

- ``jnp`` — the whole update as ONE jitted XLA elementwise chain per
  bucket. This is the default off-TPU (CPU CI) and the bit-exactness
  reference: it performs exactly the per-param path's float ops, element
  for element, so fused-vs-per-param parity is bitwise.
- ``pallas`` — a Mosaic TPU kernel over the bucket's (rows, 128) tiling
  with ``input_output_aliases`` donating params/master/moments in place
  (the reference's inplace-address-reuse story at kernel granularity).
  Scalars (lr, clip scale, beta powers) ride in SMEM. Row-block size is
  an autotune entry (``fused_optimizer_rows``; heuristic: the largest
  power-of-two divisor of the row count, capped at 512).

Beta powers are per-bucket 0-d scalars (every member of a bucket steps
together, so the per-param beta-pow arrays of the eager path collapse to
one value) and are advanced OUTSIDE the kernel — two scalar ops.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class UpdateSpec:
    """Static (trace-time) configuration of one bucket's fused update."""

    kind: str                 # "sgd" | "momentum" | "adam" | "adamw"
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    momentum: float = 0.9
    nesterov: bool = False
    rescale: float = 1.0
    decay: float = 0.0        # adamw decoupled coefficient
    reg: str | None = None    # "l2" | "l1" | None (grad-folded)
    reg_coeff: float = 0.0
    use_master: bool = False
    has_clip: bool = False    # a clip scale is applied to the grads

    @property
    def has_moment(self):
        return self.kind in ("momentum", "adam", "adamw")

    @property
    def has_adam(self):
        return self.kind in ("adam", "adamw")


def _folded_grad(spec, g, w32, scale):
    """clip scale + f32 cast + regularizer fold, mirroring the per-param
    path's op order bit for bit."""
    if spec.has_clip:
        g = (g.astype(jnp.float32) * scale).astype(g.dtype)
    g32 = g.astype(jnp.float32)
    if spec.reg == "l2" and spec.reg_coeff:
        g32 = g32 + spec.reg_coeff * w32
    elif spec.reg == "l1" and spec.reg_coeff:
        g32 = g32 + spec.reg_coeff * jnp.sign(w32)
    return g32


def _math(spec, lr, scale, w, g, master, m, v, nb1, nb2):
    """The update arithmetic shared by both implementations. ``nb1``/
    ``nb2`` are the ALREADY-advanced beta powers. Returns
    (new_w, new_master, new_m, new_v)."""
    w32 = master if spec.use_master else w.astype(jnp.float32)
    g32 = _folded_grad(spec, g, w32, scale)
    nm = nv = None
    if spec.kind == "sgd":
        new32 = w32 - lr * g32
    elif spec.kind == "momentum":
        if spec.rescale != 1.0:
            g32 = g32 * spec.rescale
        nm = spec.momentum * m + g32
        if spec.nesterov:
            new32 = w32 - lr * (g32 + spec.momentum * nm)
        else:
            new32 = w32 - lr * nm
    else:  # adam / adamw
        if spec.kind == "adamw" and spec.decay:
            w32 = w32 * (1.0 - lr * spec.decay)
        nm = spec.beta1 * m + (1 - spec.beta1) * g32
        nv = spec.beta2 * v + (1 - spec.beta2) * jnp.square(g32)
        m_hat = nm / (1 - nb1)
        v_hat = nv / (1 - nb2)
        new32 = w32 - lr * m_hat / (jnp.sqrt(v_hat) + spec.eps)
    new_w = new32.astype(w.dtype)
    new_master = new32 if spec.use_master else None
    return new_w, new_master, nm, nv


# --------------------------------------------------------------------------
# jnp implementation: the update as one elementwise chain per bucket.
# Deliberately NOT wrapped in jax.jit: under capture it traces inline
# into the step program anyway, and eagerly the op-for-op dispatch keeps
# the arithmetic bitwise identical to the per-param path (a jitted chain
# lets XLA contract mul+add into FMA, which drifts the last ulp — the
# parity suite pins bit-exactness on CPU). Still O(1) ops per bucket.
# --------------------------------------------------------------------------
def _jnp_update(spec, lr, scale, w, g, master, m, v, nb1, nb2):
    return _math(spec, lr, scale, w, g, master, m, v, nb1, nb2)


# --------------------------------------------------------------------------
# Pallas implementation: (rows, 128) tiling, in-place via aliasing
# --------------------------------------------------------------------------
def _kernel(spec, scal_ref, *refs):
    lr = scal_ref[0, 0]
    scale = scal_ref[0, 1]
    nb1 = scal_ref[0, 2]
    nb2 = scal_ref[0, 3]
    it = iter(refs)
    w_ref, g_ref = next(it), next(it)
    m_ref = next(it) if spec.has_moment else None
    v_ref = next(it) if spec.has_adam else None
    mw_ref = next(it) if spec.use_master else None
    ow_ref = next(it)
    om_ref = next(it) if spec.has_moment else None
    ov_ref = next(it) if spec.has_adam else None
    omw_ref = next(it) if spec.use_master else None

    new_w, new_master, nm, nv = _math(
        spec, lr, scale, w_ref[:], g_ref[:],
        mw_ref[:] if mw_ref is not None else None,
        m_ref[:] if m_ref is not None else None,
        v_ref[:] if v_ref is not None else None, nb1, nb2)
    ow_ref[:] = new_w
    if om_ref is not None:
        om_ref[:] = nm
    if ov_ref is not None:
        ov_ref[:] = nv
    if omw_ref is not None:
        omw_ref[:] = new_master


def pick_rows(rows: int, spec: UpdateSpec, dtype) -> int:
    """Row-block size for the kernel grid. Autotune entry
    ``fused_optimizer_rows`` when kernel autotuning is enabled;
    heuristic otherwise (largest power-of-two divisor, capped at 512 —
    ~256 KB of f32 state per step fits VMEM comfortably)."""
    cands = [c for c in (2048, 1024, 512, 256, 128, 64, 32, 16, 8)
             if c <= rows and rows % c == 0]
    if not cands:
        return rows
    heuristic = next((c for c in cands if c <= 512), cands[-1])
    from . import autotune
    if not autotune.enabled() or jax.default_backend() != "tpu":
        return heuristic
    sig = f"r{rows}|{spec.kind}|{jnp.dtype(dtype).name}|mw{spec.use_master}"

    def run(br):
        shape = (rows, 128)
        w = jnp.zeros(shape, dtype)
        g = jnp.ones(shape, dtype)
        m = jnp.zeros(shape, jnp.float32) if spec.has_moment else None
        v = jnp.zeros(shape, jnp.float32) if spec.has_adam else None
        mw = jnp.zeros(shape, jnp.float32) if spec.use_master else None
        outs = _pallas_call(spec, br, False, jnp.float32(1e-3),
                            jnp.float32(1.0), w, g, mw, m, v,
                            jnp.float32(spec.beta1),
                            jnp.float32(spec.beta2))
        jax.block_until_ready(outs)

    return autotune.autotune("fused_optimizer_rows", sig, cands, run)


def _pallas_call(spec, br, interpret, lr, scale, w2, g2, mw2, m2, v2,
                 nb1, nb2):
    from jax.experimental import pallas as pl

    rows = w2.shape[0]
    grid = (rows // br,)
    scal = jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(scale, jnp.float32),
                      jnp.asarray(nb1, jnp.float32),
                      jnp.asarray(nb2, jnp.float32)]).reshape(1, 4)

    def blk(dt):
        return pl.BlockSpec((br, 128), lambda i: (i, 0))

    try:
        from jax.experimental.pallas import tpu as pltpu
        scal_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    except Exception:  # interpret mode off-TPU
        scal_spec = pl.BlockSpec((1, 4), lambda i: (0, 0))

    ins = [w2, g2]
    in_specs = [blk(w2.dtype), blk(g2.dtype)]
    outs = [jax.ShapeDtypeStruct(w2.shape, w2.dtype)]
    out_specs = [blk(w2.dtype)]
    # inputs: 0=scal, 1=w, 2=g, then m/v/master; aliases donate in place
    aliases = {1: 0}
    nxt_in, nxt_out = 3, 1
    if spec.has_moment:
        ins.append(m2)
        in_specs.append(blk(m2.dtype))
        outs.append(jax.ShapeDtypeStruct(m2.shape, m2.dtype))
        out_specs.append(blk(m2.dtype))
        aliases[nxt_in] = nxt_out
        nxt_in += 1
        nxt_out += 1
    if spec.has_adam:
        ins.append(v2)
        in_specs.append(blk(v2.dtype))
        outs.append(jax.ShapeDtypeStruct(v2.shape, v2.dtype))
        out_specs.append(blk(v2.dtype))
        aliases[nxt_in] = nxt_out
        nxt_in += 1
        nxt_out += 1
    if spec.use_master:
        ins.append(mw2)
        in_specs.append(blk(mw2.dtype))
        outs.append(jax.ShapeDtypeStruct(mw2.shape, mw2.dtype))
        out_specs.append(blk(mw2.dtype))
        aliases[nxt_in] = nxt_out

    return pl.pallas_call(
        functools.partial(_kernel, spec),
        grid=grid,
        in_specs=[scal_spec] + in_specs,
        out_specs=out_specs,
        out_shape=outs,
        input_output_aliases=aliases,
        interpret=interpret,
        name="fused_optimizer",
    )(scal, *ins)


def _pallas_update(spec, lr, scale, w, g, master, m, v, nb1, nb2,
                   interpret):
    n = w.shape[0]
    rows = n // 128
    shape2 = (rows, 128)
    br = pick_rows(rows, spec, w.dtype)
    res = _pallas_call(
        spec, br, interpret, lr, scale, w.reshape(shape2),
        g.reshape(shape2),
        master.reshape(shape2) if master is not None else None,
        m.reshape(shape2) if m is not None else None,
        v.reshape(shape2) if v is not None else None, nb1, nb2)
    it = iter(res)
    new_w = next(it).reshape(n)
    nm = next(it).reshape(n) if spec.has_moment else None
    nv = next(it).reshape(n) if spec.has_adam else None
    new_master = next(it).reshape(n) if spec.use_master else None
    return new_w, new_master, nm, nv


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------
def fused_update(spec: UpdateSpec, *, w, g, lr, clip_scale=None,
                 master=None, m=None, v=None, b1p=None, b2p=None,
                 impl=None):
    """One fused update over a flat bucket.

    All array args are 1-D flats of equal (ALIGN-padded) length; ``lr``
    and ``clip_scale`` are f32 scalars (traced or concrete); ``b1p``/
    ``b2p`` are the bucket's CURRENT beta powers (advanced here).
    Returns ``(new_w, new_master, new_m, new_v, new_b1p, new_b2p)`` with
    ``None`` for absent slots. ``impl``: None (auto: pallas on TPU, jnp
    elsewhere) | "jnp" | "pallas" | "pallas_interpret".
    """
    lr = jnp.asarray(lr, jnp.float32)
    scale = (jnp.asarray(clip_scale, jnp.float32)
             if clip_scale is not None else jnp.float32(1.0))
    nb1 = b1p * spec.beta1 if spec.has_adam else jnp.float32(1.0)
    nb2 = b2p * spec.beta2 if spec.has_adam else jnp.float32(1.0)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if impl == "jnp":
        new_w, new_master, nm, nv = _jnp_update(
            spec, lr, scale, w, g, master, m, v, nb1, nb2)
    else:
        new_w, new_master, nm, nv = _pallas_update(
            spec, lr, scale, w, g, master, m, v, nb1, nb2,
            interpret=(impl == "pallas_interpret"))
    return (new_w, new_master, nm, nv,
            nb1 if spec.has_adam else None,
            nb2 if spec.has_adam else None)
