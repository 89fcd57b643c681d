"""Pallas TPU fused weight-only int8 matmul: ``y = (x @ W_int8) * scale``.

Capability analog of the reference's weight-only GEMMs
(``paddle/phi/kernels/fusion/cutlass/`` and
``weight_only_linear_kernel.cu``), in the operator-fusion shape argued
by PAPERS.md #3 ("Operator Fusion for LLM Inference"): the int8->float
dequantization must FUSE into the consuming matmul instead of
materializing a float weight tensor in HBM.  Weight bytes are the
serving roofline at decode (the HBM floor of a decode step is those
bytes over the bandwidth) — reading W as int8 quarters the dominant
term.

Key algebraic point: per-OUT-CHANNEL scales commute with the K
reduction (``sum_k x[m,k] * (q[k,n] * s[n]) == s[n] * sum_k x[m,k] *
q[k,n]``), so the kernel runs the MXU dot on the raw int8 block cast to
f32 and applies the scale ONCE per output tile after the reduction —
dequant costs one VPU multiply per output element instead of one per
weight element.

Two interchangeable implementations with identical arithmetic (the
fused-optimizer precedent, ``ops/pallas/fused_optimizer.py``):

- ``jnp`` — one ``dot_general`` (f32 accumulate) times the scale row.
  Deliberately UNJITTED: it is the CPU-CI implementation and the
  bit-exactness reference the interpret-mode kernel is pinned against
  (``tests/test_quantization.py``).
- ``pallas`` — grid ``(M/bm, N/bn, K/bk)`` with an f32 VMEM accumulator;
  ``bk`` covers all of K whenever it fits VMEM (the common serving
  case), making each output tile ONE dot — bitwise against the twin.
  Block sizes are an autotune entry (``quant_matmul_blocks``).

``weight_only_matmul`` is the public entry; ``quantization.
weight_only_linear`` and the ``WeightOnlyLinear`` layer route through
it, which is how a weight-quantized model served by ``models.generate``
or the continuous-batching engine reaches the fused path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MIN_SUB = 8          # f32 sublane minimum for the M tile
_LANE = 128           # lane width for the N (and padded K) tile
# bk covers all of K up to this bound; past it the K grid accumulates
# (keeps x/w blocks comfortably inside VMEM for 13B-class K)
_MAX_BK = 2048
_VMEM_CAP_BYTES = 6 * 1024 * 1024


def _cdiv(a, b):
    return (a + b - 1) // b


def _round_up(x, m):
    return _cdiv(x, m) * m


# --------------------------------------------------------------------------
# jnp twin — the arithmetic contract
# --------------------------------------------------------------------------

def _dot32(a, b):
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)


def quant_matmul_jnp(x, qw, scale, blocks=None):
    """``(x @ qw.astype(f32)) * scale`` with f32 accumulation.

    x [M, K] float, qw [K, N] int8, scale [N] float; returns [M, N]
    f32.  Unjitted on purpose (the fused-optimizer twin contract).

    ``blocks=(bm, bn, bk)`` replays the KERNEL's exact tile walk — the
    same per-tile dot shapes and ``acc += dot`` order — so interpret-
    mode parity is bitwise on every geometry (XLA's gemm is not
    guaranteed bit-stable across different tilings of one problem; the
    parity suite pins the kernel against this mirrored walk).  The
    default (None) is the one-dot form the CPU serving path uses.
    """
    sc = scale.astype(jnp.float32)
    if blocks is None:
        return _dot32(x, qw) * sc[None, :]
    bm, bn, bk = blocks
    m, k = x.shape
    n = qw.shape[1]
    if m % bm or n % bn or k % bk:
        raise ValueError(
            f"quant_matmul_jnp: blocks {blocks} must evenly divide the "
            f"(pre-padded) problem ({m}, {k}, {n}) — remainder tiles "
            f"would be silently dropped")
    rows = []
    for i in range(m // bm):
        row = []
        for j in range(n // bn):
            acc = jnp.zeros((bm, bn), jnp.float32)
            for kk in range(k // bk):
                acc = acc + _dot32(
                    x[i * bm:(i + 1) * bm, kk * bk:(kk + 1) * bk],
                    qw[kk * bk:(kk + 1) * bk, j * bn:(j + 1) * bn])
            row.append(acc * sc[None, j * bn:(j + 1) * bn])
        rows.append(jnp.concatenate(row, axis=1))
    return jnp.concatenate(rows, axis=0)


# --------------------------------------------------------------------------
# kernel
# --------------------------------------------------------------------------

def _kernel(x_ref, w_ref, s_ref, o_ref, acc_s, *, nk):
    k = pl.program_id(2) if nk > 1 else 0

    @pl.when(k == 0)
    def _init():
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    acc_s[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = acc_s[...] * s_ref[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _pallas_matmul(x, qw, scale, bm, bn, bk, interpret):
    """x [M, K] f32-castable, qw [K, N] int8, scale [N]; M/K/N already
    padded to (bm, bk|LANE, bn) multiples.  Returns [M, N] f32.

    Wrapped in a custom VJP (pallas_call has no AD rule): the backward
    runs the jnp arithmetic — ``dx = (g * s) @ qw^T``, ``ds = sum_m
    g * (x @ qw)`` — so ``jax.grad`` through ``weight_only_linear``
    keeps working on TPU exactly as it did on the unfused
    ``x @ (qw * s)`` formulation."""
    m, k = x.shape
    n = qw.shape[1]
    nk = k // bk
    grid = (m // bm, n // bn, nk)
    kernel = functools.partial(_kernel, nk=nk)
    s2 = scale.astype(jnp.float32).reshape(1, n)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, qw, s2)


def _pallas_matmul_fwd(x, qw, scale, bm, bn, bk, interpret):
    return _pallas_matmul(x, qw, scale, bm, bn, bk, interpret), \
        (x, qw, scale)


def _pallas_matmul_bwd(bm, bn, bk, interpret, res, g):
    import numpy as np
    x, qw, scale = res
    g32 = g.astype(jnp.float32)
    gs = g32 * scale.astype(jnp.float32)[None, :]
    gx = jax.lax.dot_general(
        gs, qw.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)
    acc = _dot32(x, qw)
    gscale = jnp.sum(g32 * acc, axis=0).astype(scale.dtype)
    gqw = np.zeros(qw.shape, jax.dtypes.float0)  # int8: no tangent
    return gx, gqw, gscale


_pallas_matmul.defvjp(_pallas_matmul_fwd, _pallas_matmul_bwd)


# --------------------------------------------------------------------------
# block selection (heuristic default + autotune entry)
# --------------------------------------------------------------------------

def _pick_bk(k):
    """K-block for padded ``k``: all of K when it fits (one dot per
    output tile — bitwise vs the twin), else the largest LANE multiple
    <= _MAX_BK that divides k."""
    bk = k if k <= _MAX_BK else _LANE * max(1, _MAX_BK // _LANE)
    while k % bk:
        bk -= _LANE                         # padded k is a LANE multiple
    return bk


def default_blocks(m, k, n):
    """(bm, bn, bk) for the PADDED problem: every block EVENLY divides
    its axis (the grid must tile the output exactly), one K pass when it
    fits (bitwise vs the twin and no revisits), f32 x/w/acc tiles under
    the VMEM cap."""
    bk = _pick_bk(k)
    bm = _MIN_SUB
    while bm * 2 <= min(m, 256) and m % (bm * 2) == 0:
        bm *= 2
    bn = _LANE
    # the guard prices the DOUBLED bn (w tile int8+f32 cast, x tile,
    # acc tile) — the returned blocks must respect the cap themselves
    while bn * 2 <= min(n, 512) and n % (bn * 2) == 0 and \
            (bm * bk + bk * (bn * 2) * 2 + bm * (bn * 2)) * 4 \
            <= _VMEM_CAP_BYTES:
        bn *= 2
    return bm, bn, bk


def _tune_candidates(m, k, n):
    cands = []
    bk = _pick_bk(k)      # the bk the kernel will actually run with
    for bm in (8, 32, 128, 256):
        if bm > m or m % bm:
            continue
        for bn in (128, 256, 512):
            if bn > n or n % bn:
                continue
            if (bm * bk + bk * bn * 2 + bm * bn) * 4 > _VMEM_CAP_BYTES:
                continue
            cands.append((bm, bn))
    return cands


def pick_blocks(m, k, n):
    """Block sizes through the autotune cache (entry
    ``quant_matmul_blocks``; same contract as
    ``paged_attention.pick_pages_per_block``: cache hits apply
    everywhere, the measuring sweep runs only when autotuning is
    enabled)."""
    from . import autotune as at
    bm0, bn0, bk = default_blocks(m, k, n)
    cands = _tune_candidates(m, k, n)
    if len(cands) <= 1:
        return bm0, bn0, bk
    sig = f"m{m}_k{k}_n{n}"
    try:
        cached = at._load_cache().get(
            f"{at._device_kind()}|quant_matmul_blocks|{sig}")
    except Exception:
        cached = None
    if cached is not None and list(cached) in [list(c) for c in cands]:
        return int(cached[0]), int(cached[1]), bk
    if not at.enabled():
        return bm0, bn0, bk

    import numpy as np
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    qw = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    sc = jnp.ones((n,), jnp.float32)

    def run(cand):
        jax.block_until_ready(
            _pallas_matmul(x, qw, sc, cand[0], cand[1], bk, False))

    try:
        bm, bn = at.autotune("quant_matmul_blocks", sig, cands, run)
        return int(bm), int(bn), bk
    except Exception:
        return bm0, bn0, bk


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def weight_only_matmul(x, qw, scale, bias=None, impl=None,
                       interpret=None):
    """``x @ dequant(qw, scale) [+ bias]`` without materializing the
    float weights: dequant fuses into the matmul at int8 read width.

    x [..., K] float (any leading dims), qw [K, N] int8, scale [N]
    float, bias [N] or None.  Accumulation is f32; the result is cast
    back to ``x.dtype`` before the bias add (matching the unfused
    ``x @ (q * s)`` path at f32, and bounding bf16 error by ONE final
    rounding).  ``impl``: None (auto: pallas on TPU, jnp twin
    elsewhere) | "jnp" | "pallas" | "pallas_interpret".
    """
    x = jnp.asarray(x)
    qw = jnp.asarray(qw)
    scale = jnp.asarray(scale)
    *lead, k = x.shape
    n = qw.shape[1]
    if qw.shape[0] != k:
        raise ValueError(
            f"weight_only_matmul: x K dim {k} != weight rows {qw.shape[0]}")
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    x2 = x.reshape(-1, k)
    if impl == "jnp":
        y = quant_matmul_jnp(x2, qw, scale)
    else:
        m = x2.shape[0]
        mp = _round_up(max(m, 1), _MIN_SUB)
        kp = _round_up(k, _LANE)
        npad = _round_up(n, _LANE)
        xp = x2 if (mp, kp) == (m, k) else jnp.pad(
            x2, ((0, mp - m), (0, kp - k)))
        wp = qw if (kp, npad) == (k, n) else jnp.pad(
            qw, ((0, kp - k), (0, npad - n)))
        sp = scale if npad == n else jnp.pad(scale, (0, npad - n))
        bm, bn, bk = pick_blocks(mp, kp, npad)
        y = _pallas_matmul(xp, wp, sp, bm, bn, bk,
                           interpret=(impl == "pallas_interpret"))
        y = y[:m, :n]
    y = y.astype(x.dtype)
    if bias is not None:
        y = y + jnp.asarray(bias).astype(x.dtype)
    return y.reshape(*lead, n)
