"""Fused flash-attention BACKWARD parity suite (ISSUE 11).

Two contracts, the quant_matmul discipline:

1. BITWISE — the fused Pallas backward in interpret mode produces grads
   bit-identical to ``flash_attention_bwd_jnp``, the unjitted twin that
   replays the kernel's exact tile walk, on every tested geometry
   (causal x GQA x segment-ids x padded tails x rectangles x bf16).
2. ACCURATE — the same grads match ``jax.grad`` of the plain-XLA
   reference attention within tolerance (the twin being bit-faithful to
   a wrong kernel would pass contract 1 alone).

Everything is model-free and runs tiny shapes; the suite is pinned in
conftest's dense tier-1 window.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


def _rand(shape, dtype=jnp.float32, seed=0, scale=0.3):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, dtype)


def _pallas_bwd(q, k, v, do, causal, blocks, segment_ids=None):
    """Interpret-mode fused backward grads via the real custom_vjp, plus
    the forward residuals the twin needs."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    qt, kt, vt, dot = (jnp.swapaxes(x, 1, 2) for x in (q, k, v, do))
    seg_q = seg_k = None
    if segment_ids is not None:
        seg_q, seg_k = segment_ids
        seg_q = jnp.asarray(seg_q, jnp.int32)
        seg_k = jnp.asarray(seg_k, jnp.int32)
    o, vjp = jax.vjp(
        lambda a, b, c: fa._flash_bhsd(a, b, c, seg_q, seg_k, scale,
                                       causal, True, blocks, blocks),
        qt, kt, vt)
    dq, dk, dv = vjp(dot)
    _, lse = fa._fwd(qt, kt, vt, seg_q, seg_k, scale, causal, True, blocks)
    grads = tuple(jnp.swapaxes(g, 1, 2) for g in (dq, dk, dv))
    return grads, jnp.swapaxes(o, 1, 2), lse, scale


def _assert_bitwise(pallas_grads, twin_grads):
    for name, a, b in zip(("dq", "dk", "dv"), pallas_grads, twin_grads):
        assert a.dtype == b.dtype, name
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{name} drifted from the jnp twin (max abs diff " \
            f"{np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)).max():.3e})"


# geometry grid: (batch, hq, hk, sq, sk, d, causal, (bq, bk))
# - multi-block walks in both grid dims (the accumulate paths)
# - GQA head folding (rep > 1)
# - padded q and k tails (sq/sk not multiples of the blocks)
# - rectangles both ways (sk > sq streams extra k blocks; sq > sk has
#   rows that attend nothing — the hi<=0 flush clamp)
_GEOMETRIES = [
    pytest.param(2, 4, 4, 64, 64, 16, True, (16, 16), id="causal-multiblock"),
    pytest.param(2, 4, 4, 64, 64, 16, False, (16, 16), id="full-multiblock"),
    pytest.param(1, 4, 2, 50, 50, 8, True, (16, 16), id="gqa-padded-tail"),
    pytest.param(1, 6, 2, 40, 40, 8, False, (16, 16), id="gqa3-padded-full"),
    pytest.param(1, 2, 2, 32, 64, 8, True, (16, 32), id="rect-sk-long"),
    pytest.param(1, 2, 2, 64, 32, 8, True, (16, 16), id="rect-sq-long"),
    pytest.param(1, 2, 2, 48, 80, 8, True, (16, 32), id="asym-blocks"),
    pytest.param(1, 2, 2, 33, 47, 8, False, (16, 16), id="both-tails-padded"),
]


@pytest.mark.parametrize("b,hq,hk,sq,sk,d,causal,blocks", _GEOMETRIES)
def test_fused_bwd_bitwise_vs_twin(b, hq, hk, sq, sk, d, causal, blocks):
    q = _rand((b, sq, hq, d), seed=1)
    k = _rand((b, sk, hk, d), seed=2)
    v = _rand((b, sk, hk, d), seed=3)
    do = _rand((b, sq, hq, d), seed=4)
    grads, o, lse, scale = _pallas_bwd(q, k, v, do, causal, blocks)
    twin = fa.flash_attention_bwd_jnp(q, k, v, do, o, lse, scale=scale,
                                      causal=causal, blocks=blocks)
    _assert_bitwise(grads, twin)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_bwd_bitwise_segments(causal):
    """Varlen/packed segments (q and kv id vectors differ in length)."""
    b, sq, sk, h, d = 1, 48, 64, 2, 8
    rng = np.random.default_rng(7)
    seg_q = np.sort(rng.integers(0, 3, (b, sq)), axis=1)
    seg_k = np.sort(rng.integers(0, 3, (b, sk)), axis=1)
    q = _rand((b, sq, h, d), seed=1)
    k = _rand((b, sk, h, d), seed=2)
    v = _rand((b, sk, h, d), seed=3)
    do = _rand((b, sq, h, d), seed=4)
    grads, o, lse, scale = _pallas_bwd(q, k, v, do, causal, (16, 16),
                                       segment_ids=(seg_q, seg_k))
    twin = fa.flash_attention_bwd_jnp(
        q, k, v, do, o, lse, scale=scale, causal=causal,
        segment_ids=(seg_q, seg_k), blocks=(16, 16))
    _assert_bitwise(grads, twin)


def test_fused_bwd_bitwise_bf16():
    """bf16 inputs: f32 in-kernel accumulation, one final cast — the
    cast order must match the twin bit-for-bit too."""
    q = _rand((1, 64, 2, 16), jnp.bfloat16, seed=1)
    k = _rand((1, 64, 2, 16), jnp.bfloat16, seed=2)
    v = _rand((1, 64, 2, 16), jnp.bfloat16, seed=3)
    do = _rand((1, 64, 2, 16), jnp.bfloat16, seed=4)
    grads, o, lse, scale = _pallas_bwd(q, k, v, do, True, (16, 16))
    assert grads[0].dtype == jnp.bfloat16
    twin = fa.flash_attention_bwd_jnp(q, k, v, do, o, lse, scale=scale,
                                      causal=True, blocks=(16, 16))
    _assert_bitwise(grads, twin)


def test_fused_bwd_bitwise_gqa_bf16_padded():
    """The union of the hard paths in one geometry: GQA head-sum, bf16
    casts, padded q tail, multi-k accumulation."""
    q = _rand((2, 50, 4, 8), jnp.bfloat16, seed=11)
    k = _rand((2, 50, 2, 8), jnp.bfloat16, seed=12)
    v = _rand((2, 50, 2, 8), jnp.bfloat16, seed=13)
    do = _rand((2, 50, 4, 8), jnp.bfloat16, seed=14)
    grads, o, lse, scale = _pallas_bwd(q, k, v, do, True, (16, 16))
    twin = fa.flash_attention_bwd_jnp(q, k, v, do, o, lse, scale=scale,
                                      causal=True, blocks=(16, 16))
    _assert_bitwise(grads, twin)


# ---------------------------------------------------------------- ref --
def _ref_sdpa(q, k, v, causal):
    from paddle_tpu.nn.functional.attention import _sdpa_xla
    return _sdpa_xla(q, k, v, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hk", [(2, 2), (4, 2)])
def test_fused_bwd_matches_reference_grad(causal, hq, hk):
    """Fused backward vs jax.grad of the plain-XLA attention (the
    accuracy leg — bitwise-vs-twin alone can't catch a faithful replay
    of wrong math)."""
    q = _rand((1, 37, hq, 32), seed=4, scale=1.0)
    k = _rand((1, 37, hk, 32), seed=5, scale=1.0)
    v = _rand((1, 37, hk, 32), seed=6, scale=1.0)

    def loss_pl(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                               blocks=(16, 16), bwd_blocks=(16, 16))
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_sdpa(q, k, v, causal)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_fused_bwd_distinct_blocks_same_values():
    """The bwd_blocks free parameter changes the tile walk, not the
    math: grads across block choices agree to accumulation-order
    tolerance, and each matches its own twin bitwise."""
    q = _rand((1, 64, 2, 16), seed=21)
    k = _rand((1, 64, 2, 16), seed=22)
    v = _rand((1, 64, 2, 16), seed=23)
    do = _rand((1, 64, 2, 16), seed=24)
    ref = None
    for blocks in ((16, 16), (32, 16), (16, 32), (64, 64)):
        grads, o, lse, scale = _pallas_bwd(q, k, v, do, True, blocks)
        twin = fa.flash_attention_bwd_jnp(q, k, v, do, o, lse,
                                          scale=scale, causal=True,
                                          blocks=blocks)
        _assert_bitwise(grads, twin)
        if ref is None:
            ref = grads
        else:
            for a, b in zip(ref, grads):
                np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_bwd_autotune_candidates_registered():
    """The flash_attention_bwd entry exists with backward-specific
    candidates that reach the measured default and no further (the
    vmem-footprint rationale), and the public API threads bwd_blocks
    through."""
    assert fa._bwd_block_sizes(8192, 8192, True) in fa._TUNE_BWD_CANDIDATES
    assert max(c[0] for c in fa._TUNE_BWD_CANDIDATES) <= 1024
    assert max(c[1] for c in fa._TUNE_BWD_CANDIDATES) <= 1024
    # a cached winner under the entry is honored on a later call
    import paddle_tpu.ops.pallas.autotune as at
    key = f"{at._device_kind()}|flash_attention_bwd|b1h2sq512sk512d16c1"
    cache = at._load_cache()
    old = dict(cache)
    try:
        cache[key] = [256, 256]
        q = jnp.zeros((1, 2, 512, 16), jnp.float32)
        got = fa._autotuned_bwd_blocks(q, q, 0.25, True, None)
        assert got == (256, 256)
    finally:
        cache.clear()
        cache.update(old)


# ------------------------------------------- what a tile is given (PR 32) --
# geometries at block pairs where plain, masked and skipped tiles all occur
# (the causal ones), and where a kind is missing altogether:
# (hq, hk, sq, sk, d, causal, blocks, segments)
_TILE_GEOMETRIES = [
    pytest.param(2, 2, 128, 128, 32, True, (32, 32), False, id="causal"),
    pytest.param(2, 2, 100, 100, 32, False, (32, 32), False,
                 id="full-padded-tails"),
    pytest.param(4, 2, 112, 112, 32, True, (32, 32), False,
                 id="gqa-causal-padded"),
    pytest.param(2, 2, 64, 128, 32, True, (32, 32), False, id="sq-lt-sk"),
    pytest.param(2, 2, 128, 128, 32, True, (32, 64), False,
                 id="causal-wide-k"),
    pytest.param(2, 2, 128, 128, 32, True, (32, 32), True, id="segments"),
    pytest.param(4, 1, 64, 64, 32, False, (32, 32), False,
                 id="gqa-full-all-plain"),
    pytest.param(2, 1, 96, 96, 128, True, (32, 32), False,
                 id="head-dim-128"),
]


def _tile_inputs(hq, hk, sq, sk, d, segments, dtype=jnp.bfloat16):
    q = _rand((1, sq, hq, d), dtype, seed=31, scale=1.0)
    k = _rand((1, sk, hk, d), dtype, seed=32, scale=1.0)
    v = _rand((1, sk, hk, d), dtype, seed=33, scale=1.0)
    w = _rand((1, sq, hq, d), jnp.float32, seed=34, scale=1.0)
    seg = None
    if segments:
        seg = jnp.asarray(np.sort(
            np.random.default_rng(35).integers(0, 3, (1, sq)), axis=1))
    return q, k, v, w, seg


def _count_tiles(kernel, sq, sk, causal, blocks, segments):
    """Brute force over the elements: a tile is skipped when causal and
    none of its elements lies on or under the diagonal.  The backward
    runs a tile plain when all of its elements do, all are in range and
    there are no segment ids, and masked otherwise; the forward has one
    body for the tiles of a call, masked when any of them can need it."""
    bq, bk = blocks
    nq, nk = -(-sq // bq), -(-sk // bk)
    rows = np.arange(nq * bq)[:, None]
    cols = np.arange(nk * bk)[None, :]
    under = (rows + (sk - sq) >= cols) if causal \
        else np.ones((nq * bq, nk * bk), bool)
    inside = (cols < sk) & (rows < sq)
    n = {"plain": 0, "masked": 0, "skipped": 0}
    for iq in range(nq):
        for ik in range(nk):
            t = np.s_[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            if not under[t].any():
                n["skipped"] += 1
            elif kernel == "fwd":
                n["masked" if causal or segments or sk % bk
                  else "plain"] += 1
            elif not segments and (under[t] & inside[t]).all():
                n["plain"] += 1
            else:
                n["masked"] += 1
    return n


@pytest.mark.parametrize("hq,hk,sq,sk,d,causal,blocks,segments",
                         _TILE_GEOMETRIES)
def test_tile_gauges_equal_a_brute_force_count(hq, hk, sq, sk, d, causal,
                                               blocks, segments):
    """``flash.tiles{kernel, kind, shape}`` is what the loop bounds and
    the predicates make of the geometry: tiles a call, batch x heads x
    a row's."""
    from paddle_tpu.observability import metrics
    q, k, v, w, seg = _tile_inputs(hq, hk, sq, sk, d, segments)
    jax.grad(lambda a: (fa.flash_attention(
        a, k, v, causal=causal, interpret=True, blocks=blocks,
        bwd_blocks=blocks, segment_ids=seg).astype(jnp.float32)
        * w).sum())(q)
    shape = (f"b1h{hq}sq{sq}sk{sk}d{d}c{int(causal)}s{int(segments)}"
             f".{blocks[0]}x{blocks[1]}")
    for kernel in ("fwd", "bwd"):
        want = _count_tiles(kernel, sq, sk, causal, blocks, segments)
        got = {kind: metrics.registry().gauge(
            "flash.tiles", labels={"kernel": kernel, "kind": kind,
                                   "shape": shape}).value / hq
            for kind in want}
        assert got == want, (kernel, got, want)
    if causal and not segments and d != 128:
        assert all(want.values()), want     # the backward has every kind


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


@pytest.mark.parametrize("hq,hk,sq,sk,d,causal,blocks,segments",
                         _TILE_GEOMETRIES)
def test_bf16_grads_as_close_as_unfused_bf16(hq, hk, sq, sk, d, causal,
                                             blocks, segments):
    """bfloat16 operands into every product (float32 accumulation and
    statistics): each gradient's relative error against float32
    attention is within 1.25 x that of the repo's unfused bfloat16
    attention, which also rounds the probabilities before its second
    product; and the two bfloat16 paths differ by no more than their
    errors together."""
    from paddle_tpu.nn.functional.attention import _sdpa_xla
    q, k, v, w, seg = _tile_inputs(hq, hk, sq, sk, d, segments)
    mask = None if seg is None else \
        (seg[:, :, None] == seg[:, None, :])[:, None]

    def grads(fn, *xs):
        return jax.grad(lambda a, b, c: (
            fn(a, b, c).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2))(*xs)

    kernel = grads(lambda a, b, c: fa.flash_attention(
        a, b, c, causal=causal, interpret=True, blocks=blocks,
        bwd_blocks=blocks, segment_ids=seg), q, k, v)
    unfused = grads(lambda a, b, c: _sdpa_xla(
        a, b, c, mask=mask, causal=causal), q, k, v)
    exact = grads(lambda a, b, c: _sdpa_xla(
        a, b, c, mask=mask, causal=causal),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, gk, gu, ge in zip(("dq", "dk", "dv"), kernel, unfused, exact):
        assert gk.dtype == jnp.bfloat16, name
        err_k, err_u = _rel(gk, ge), _rel(gu, ge)
        assert err_k <= 1.25 * err_u, (name, err_k, err_u)
        assert _rel(gk, gu) <= err_k + err_u, name


def _kernel_dots(fn, *args):
    """(operand dtypes, result dtype) of every ``dot_general`` inside
    the Pallas kernels of ``fn``'s jaxpr, loops and branches included."""
    def walk(jaxpr, inside):
        for eqn in jaxpr.eqns:
            if inside and eqn.primitive.name == "dot_general":
                yield (tuple(str(x.aval.dtype) for x in eqn.invars),
                       str(eqn.outvars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(
                    sub, inside or eqn.primitive.name == "pallas_call")
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr, False))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_kernel_products_take_the_tensors_dtype(direction, dtype):
    """Every product of both kernels takes operands in the inputs'
    dtype and accumulates in float32: two products in the forward's
    tile body, five in each of the backward's two (plain and masked)."""
    q, k, v, w, _ = _tile_inputs(2, 2, 128, 128, 32, False,
                                 jnp.dtype(dtype))

    def forward(a, b, c):
        return fa.flash_attention(a, b, c, causal=True, interpret=True,
                                  blocks=(32, 32), bwd_blocks=(32, 32))

    if direction == "forward":
        dots = _kernel_dots(forward, q, k, v)
    else:
        both = _kernel_dots(jax.grad(lambda a, b, c: (
            forward(a, b, c).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2)), q, k, v)
        dots = both[2:]
        assert both[:2] == _kernel_dots(forward, q, k, v)
    assert len(dots) == (2 if direction == "forward" else 10)
    assert set(dots) == {((dtype, dtype), "float32")}, dots


def test_scaled_q_is_rounded_once_and_within_half_an_ulp():
    """``q * scale`` becomes a product operand: exact in float32 and,
    at a power-of-two scale (head_dim 64), in bfloat16; at head_dim 128
    within half a bfloat16 ulp (at most 2**-8 of the value)."""
    x32 = _rand((64, 128), jnp.float32, seed=41, scale=1.0)
    x16 = x32.astype(jnp.bfloat16)
    for scale in (0.125, 1.0 / math.sqrt(128)):
        assert np.array_equal(fa._scaled(x32, scale), x32 * scale)
        assert fa._scaled(x16, scale).dtype == jnp.bfloat16
        got = np.asarray(fa._scaled(x16, scale), np.float64)
        want = np.asarray(x16, np.float64) * scale
        if scale == 0.125:
            assert np.array_equal(got, want)
        else:
            assert (np.abs(got - want) <= 1.001 * 2.0 ** -8 * np.abs(want)).all()
            assert not np.array_equal(got, want)
