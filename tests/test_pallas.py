"""Pallas fused-kernel correctness tests (interpreter mode on CPU).

The reference validates its fused CUDA kernels against unfused compositions
(e.g. ``test/legacy_test/test_flash_attention.py`` checks flash_attn vs a
naive softmax attention); we do the same: each Pallas kernel is compared —
forward and gradients — against the plain-XLA composition it replaces.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas import norms, rope


def _ref_sdpa(q, k, v, causal):
    from paddle_tpu.nn.functional.attention import _sdpa_xla
    return _sdpa_xla(q, k, v, causal=causal)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), dtype=dtype)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_forward(causal):
    q = _rand((2, 70, 4, 32), seed=1)
    k = _rand((2, 70, 4, 32), seed=2)
    v = _rand((2, 70, 4, 32), seed=3)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref_sdpa(q, k, v, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_cross_lengths():
    # kv longer than q (decode-with-prefix shape): causal offset path
    q = _rand((1, 17, 2, 32), seed=1)
    k = _rand((1, 40, 2, 32), seed=2)
    v = _rand((1, 40, 2, 32), seed=3)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_sdpa(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_gqa():
    q = _rand((2, 33, 8, 32), seed=1)
    k = _rand((2, 33, 2, 32), seed=2)
    v = _rand((2, 33, 2, 32), seed=3)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_sdpa(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    q = _rand((1, 37, 2, 32), seed=4)
    k = _rand((1, 37, 2, 32), seed=5)
    v = _rand((1, 37, 2, 32), seed=6)

    def loss_pl(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_sdpa(q, k, v, causal)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_attention_gqa_grads():
    q = _rand((1, 21, 4, 32), seed=7)
    k = _rand((1, 21, 2, 32), seed=8)
    v = _rand((1, 21, 2, 32), seed=9)

    def loss_pl(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, interpret=True)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_ref_sdpa(q, k, v, True)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_attention_multiblock_grads():
    # seq > 128: multiple q/k blocks + padding (the tiled code paths the
    # single-block shapes above never reach)
    q = _rand((1, 300, 2, 16), seed=10)
    k = _rand((1, 300, 2, 16), seed=11)
    v = _rand((1, 300, 2, 16), seed=12)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_sdpa(q, k, v, True)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def loss_pl(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, interpret=True)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.square(_ref_sdpa(q, k, v, True)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def _ref_segmented(q, k, v, seg_q, seg_k, causal):
    from paddle_tpu.nn.functional.attention import _sdpa_xla
    mask = seg_q[:, None, :, None] == seg_k[:, None, None, :]
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        iq = jnp.arange(sq)[:, None] + (sk - sq)
        mask = mask & (iq >= jnp.arange(sk)[None, :])[None, None]
    return _sdpa_xla(q, k, v, mask=mask, causal=False)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_ids(causal):
    """Varlen via segment ids (the reference flash_attn_varlen capability):
    attention confined to same-segment pairs, parity vs masked XLA."""
    B, S, H, D = 2, 96, 4, 32
    q = _rand((B, S, H, D), seed=1)
    k = _rand((B, S, H, D), seed=2)
    v = _rand((B, S, H, D), seed=3)
    # ragged packing: row 0 -> [40, 56], row 1 -> [10, 30, 56]
    seg = np.zeros((B, S), np.int32)
    seg[0, 40:] = 1
    seg[1, 10:40] = 1
    seg[1, 40:] = 2
    seg = jnp.asarray(seg)
    out = fa.flash_attention(q, k, v, causal=causal, interpret=True,
                             segment_ids=seg)
    ref = _ref_segmented(q, k, v, seg, seg, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_segment_ids_grads():
    B, S, H, D = 1, 64, 2, 32
    q = _rand((B, S, H, D), seed=4)
    k = _rand((B, S, H, D), seed=5)
    v = _rand((B, S, H, D), seed=6)
    seg = jnp.asarray(np.repeat([[0, 1]], B, 0).repeat(S // 2, axis=1))

    def loss_pl(q, k, v):
        o = fa.flash_attention(q, k, v, causal=True, interpret=True,
                               segment_ids=seg)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(_ref_segmented(q, k, v, seg, seg, True)))

    gp = jax.grad(loss_pl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_attention_segment_ids_gqa_multiblock():
    # segments spanning block boundaries + GQA head mapping
    B, S, H, D = 1, 300, 4, 32
    q = _rand((B, S, H, D), seed=7)
    k = _rand((B, S, 2, D), seed=8)
    v = _rand((B, S, 2, D), seed=9)
    seg = np.zeros((B, S), np.int32)
    seg[0, 130:] = 1
    seg[0, 250:] = 2
    seg = jnp.asarray(seg)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True,
                             segment_ids=seg, blocks=(128, 128))
    ref = _ref_segmented(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2),
                         seg, seg, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attn_unpadded_functional():
    """paddle.nn.functional.flash_attn_unpadded parity: packed rows with
    cu_seqlens match per-sequence dense attention."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(0)
    lens = [24, 40]
    total, H, D = sum(lens), 2, 16
    q = rng.normal(size=(total, H, D)).astype(np.float32)
    k = rng.normal(size=(total, H, D)).astype(np.float32)
    v = rng.normal(size=(total, H, D)).astype(np.float32)
    cu = np.cumsum([0] + lens).astype(np.int32)
    out, _ = F.flash_attn_unpadded(
        paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
        paddle.to_tensor(cu), paddle.to_tensor(cu),
        max(lens), max(lens), causal=True)
    out = out.numpy()
    # each packed sequence == standalone causal attention
    from paddle_tpu.nn.functional.attention import _sdpa_xla
    for i, ln in enumerate(lens):
        s, e = cu[i], cu[i + 1]
        ref = _sdpa_xla(jnp.asarray(q[None, s:e]), jnp.asarray(k[None, s:e]),
                        jnp.asarray(v[None, s:e]), causal=True)[0]
        np.testing.assert_allclose(out[s:e], ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    q = _rand((1, 64, 2, 64), jnp.bfloat16, seed=1)
    k = _rand((1, 64, 2, 64), jnp.bfloat16, seed=2)
    v = _rand((1, 64, 2, 64), jnp.bfloat16, seed=3)
    out = fa.flash_attention(q, k, v, causal=True, interpret=True)
    ref = _ref_sdpa(q, k, v, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("sq,sk,causal,blocks", [
    pytest.param(128, 128, True, (32, 32), id="causal"),
    pytest.param(64, 128, True, (32, 64), id="causal-sq-lt-sk"),
    pytest.param(100, 100, True, (32, 32), id="causal-padded"),
    pytest.param(100, 100, False, (32, 32), id="full-padded"),
    pytest.param(128, 128, False, (32, 32), id="full-all-plain"),
])
def test_flash_attention_plain_tiles_equal_masked_tiles(sq, sk, causal,
                                                        blocks, dtype):
    """The backward runs a tile wholly under the diagonal through a body
    with no mask, and the forward of a call no tile of which can need a
    mask has none; under segment ids every tile builds its mask, as
    every tile did before the kinds were told apart.  With one segment
    the two calls do the same arithmetic: outputs and gradients are
    equal to the bit."""
    q = _rand((1, sq, 2, 32), dtype, seed=51)
    k = _rand((1, sk, 2, 32), dtype, seed=52)
    v = _rand((1, sk, 2, 32), dtype, seed=53)
    one = (jnp.zeros((1, sq), jnp.int32), jnp.zeros((1, sk), jnp.int32))

    def run(seg):
        return jax.value_and_grad(lambda a, b, c: fa.flash_attention(
            a, b, c, causal=causal, interpret=True, blocks=blocks,
            bwd_blocks=blocks, segment_ids=seg).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    (out, grads), (out_m, grads_m) = run(None), run(one)
    assert np.array_equal(out, out_m)
    for g, gm in zip(grads, grads_m):
        assert g.dtype == jnp.dtype(dtype)
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(gm, np.float32))


# --------------------------------------------------------------------------
def _ref_rms(x, w, eps=1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _ref_ln(x, w, b, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return out.astype(x.dtype) * w + b


def test_rms_norm_fwd_bwd():
    x = _rand((6, 37, 128), seed=1)
    w = _rand((128,), seed=2) + 1.0

    out = norms.rms_norm(x, w, interpret=True)
    np.testing.assert_allclose(out, _ref_rms(x, w), atol=1e-5, rtol=1e-5)

    def lp(x, w):
        return jnp.sum(jnp.sin(norms.rms_norm(x, w, interpret=True)))

    def lr(x, w):
        return jnp.sum(jnp.sin(_ref_rms(x, w)))

    gp = jax.grad(lp, argnums=(0, 1))(x, w)
    gr = jax.grad(lr, argnums=(0, 1))(x, w)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_layer_norm_fwd_bwd():
    x = _rand((300, 64), seed=3)  # non-multiple of row block: padding path
    w = _rand((64,), seed=4) + 1.0
    b = _rand((64,), seed=5)

    out = norms.layer_norm(x, w, b, interpret=True)
    np.testing.assert_allclose(out, _ref_ln(x, w, b), atol=1e-5, rtol=1e-5)

    def lp(x, w, b):
        return jnp.sum(jnp.cos(norms.layer_norm(x, w, b, interpret=True)))

    def lr(x, w, b):
        return jnp.sum(jnp.cos(_ref_ln(x, w, b)))

    gp = jax.grad(lp, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, w, b)
    for a, b_ in zip(gp, gr):
        np.testing.assert_allclose(a, b_, atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
def _rope_tables(s, d, base=10000.0):
    inv = 1.0 / base ** (np.arange(0, d // 2) * 2.0 / d)
    ang = np.arange(s)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)  # neox tiling
    return jnp.asarray(np.cos(ang), jnp.float32), \
        jnp.asarray(np.sin(ang), jnp.float32)


def _ref_rope_neox(x, cos, sin):
    d = x.shape[-1]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * c + rot * s


def test_rope_interleaved():
    # pair (2i, 2i+1): rot[2i] = -x[2i+1], rot[2i+1] = x[2i]
    x = _rand((1, 16, 2, 32), seed=8)
    d = 32
    inv = 1.0 / 10000.0 ** (np.arange(0, d // 2) * 2.0 / d)
    ang = np.repeat(np.arange(16)[:, None] * inv[None, :], 2, axis=-1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    out = rope.apply_rope(x, cos, sin, use_neox=False, interpret=True)
    xe = np.asarray(x).reshape(1, 16, 2, d // 2, 2)
    rot = np.stack([-xe[..., 1], xe[..., 0]], -1).reshape(1, 16, 2, d)
    ref = np.asarray(x) * np.asarray(cos)[None, :, None, :] + \
        rot * np.asarray(sin)[None, :, None, :]
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_rope_batched_tables():
    # per-example tables [B, S, D] (the position_ids path)
    x = _rand((2, 8, 2, 16), seed=9)
    cos, sin = _rope_tables(32, 16)
    pid = np.stack([np.arange(8), np.arange(8) + 3])  # shifted positions
    cb = jnp.asarray(np.asarray(cos)[pid])
    sb = jnp.asarray(np.asarray(sin)[pid])
    out = rope.apply_rope(x, cb, sb, interpret=True)
    for b in range(2):
        ref = _ref_rope_neox(x[b:b + 1], cb[b], sb[b])
        np.testing.assert_allclose(out[b:b + 1], ref, atol=1e-5, rtol=1e-5)


def test_rope_fwd_bwd():
    x = _rand((2, 48, 4, 64), seed=6)
    cos, sin = _rope_tables(48, 64)

    out = rope.apply_rope(x, cos, sin, interpret=True)
    ref = _ref_rope_neox(x, cos, sin)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def lp(x):
        return jnp.sum(jnp.sin(rope.apply_rope(x, cos, sin, interpret=True)))

    def lr(x):
        return jnp.sum(jnp.sin(_ref_rope_neox(x, cos, sin)))

    np.testing.assert_allclose(jax.grad(lp)(x), jax.grad(lr)(x),
                               atol=2e-5, rtol=2e-5)


# the half turn, position-tiled (``rope.half_turn``: the training path's
# kernel at head width 128, ``models/lfm2.py`` ``_rotate`` on a TPU)
def _half_turn_jnp(x, cos, sin):
    """``_rotate``'s plain form on one operand."""
    from paddle_tpu.models.lfm2 import _rotate
    import paddle_tpu as paddle
    return _rotate(paddle.Tensor(x), paddle.Tensor(x), cos, sin)[0]._data


def _half_turn_tables(n, r):
    """Plain tables 128 wide; YaRN's, scaled by its factor, 64 wide
    (Laguna's full layers)."""
    from paddle_tpu.models.llama import rope_angles, yarn_inv_freq
    if r == 128:
        return rope_angles(np.arange(n), r, 10000.0)
    return rope_angles(np.arange(n), r, 500000.0,
                       inv_freq=yarn_inv_freq(r, 500000.0, 64, 4096, 32, 1),
                       scale=1.4159)


def _assert_within_one_unit(got, want, operand, scale=1.0):
    """Equal to the last bit, or one unit of the operands' type in the
    last place at the size of the terms summed: the head's largest
    operand times the tables' scale (where two products cancel, a
    multiply-add that rounds once and one that rounds twice differ by a
    unit of the products, not of their small sum)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    bits = jnp.finfo(want.dtype).nmant
    got, want, operand = (np.asarray(a, np.float64)
                          for a in (got, want, operand))
    size = np.maximum(np.abs(want), scale * np.abs(operand).max(
        axis=-1, keepdims=True))
    assert (np.abs(got - want) <= 2.0 ** (np.floor(np.log2(size)) - bits)).all()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("heads", [64, 48, 32, 8])
@pytest.mark.parametrize("r", [128, 64])
def test_half_turn_is_the_jnp_form_fwd_and_bwd(r, heads, dtype):
    """A head of 128 whose first ``r`` dimensions turn, at the cells'
    head counts: the kernel's result is the jnp form's, its gradient is
    the jnp form's autodiff gradient, and between forward and backward
    it keeps the two tables it reads there and nothing of ``x``."""
    n = 64
    cos, sin = _half_turn_tables(n, r)
    x = _rand((1, n, heads, 128), dtype, seed=r + heads)
    w = _rand((1, n, heads, 128), dtype, seed=1)

    def kernel(x):
        return rope.half_turn(x, cos, sin, interpret=True)

    want, vjp_jnp = jax.vjp(lambda x: _half_turn_jnp(x, cos, sin), x)
    got, vjp = jax.vjp(kernel, x)
    scale = float(jnp.abs(cos).max())
    _assert_within_one_unit(got, want, x, scale)
    if r < 128:
        assert jnp.array_equal(got[..., r:], x[..., r:])
    _assert_within_one_unit(vjp(w)[0], vjp_jnp(w)[0], w, scale)
    kept = jax.tree_util.tree_leaves(vjp)
    assert sorted((a.shape, a.dtype) for a in kept) \
        == [((n, 128), jnp.float32)] * 2
    # one kernel each way, by the names a trace is read by
    text = str(jax.make_jaxpr(lambda x, w: jax.vjp(kernel, x)[1](w))(x, w))
    assert text.count("name=rope_half_turn_fwd") == 1
    assert text.count("name=rope_half_turn_bwd") == 1


def test_half_turn_tables_are_made_once_and_refuse_an_odd_width():
    cos, sin = _half_turn_tables(32, 64)
    first = rope.turn_tables(cos, sin)
    assert all(a is b for a, b in zip(first, rope.turn_tables(cos, sin)))
    c, s, st = (np.asarray(a) for a in first)
    assert (c[:, 64:] == 1).all() and not s[:, 64:].any()
    assert np.array_equal(st[:, :32], s[:, 32:64])
    assert np.array_equal(st[:, 32:64], s[:, :32])
    with pytest.raises(ValueError, match="halves"):
        rope.turn_tables(cos[:, :63], sin[:, :63])
    with pytest.raises(ValueError, match="half_turn"):
        rope.half_turn(_rand((1, 32, 2, 64)), cos, sin, interpret=True)


@pytest.mark.parametrize("head,calls", [(64, 0), (128, 2)])
def test_rotate_calls_the_kernel_in_a_tpu_program_at_head_width_128_alone(
        monkeypatch, head, calls):
    """``_rotate`` chooses on the head's width, the backend and whether
    a program is being captured: at 128 in a program captured on a TPU
    q and k each go through the kernel; at 64 (LFM2's heads), off the
    TPU and in per-op dispatch neither does."""
    import paddle_tpu as paddle
    from paddle_tpu.core import scope
    from paddle_tpu.models.lfm2 import _rotate
    from paddle_tpu.models.llama import rope_angles
    cos, sin = rope_angles(np.arange(32), head, 1e6)
    q = paddle.Tensor(_rand((1, 32, 4, head), jnp.bfloat16, seed=3))
    k = paddle.Tensor(_rand((1, 32, 2, head), jnp.bfloat16, seed=4))
    seen, real = [], rope.half_turn
    monkeypatch.setattr(rope, "half_turn", lambda x, c, s: seen.append(
        x.shape) or real(x, c, s, interpret=True))
    plain = _rotate(q, k, cos, sin)
    with scope.capture():               # captured, off the TPU
        _rotate(q, k, cos, sin)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _rotate(q, k, cos, sin)             # on it, per-op dispatch
    assert not seen
    with scope.capture():
        steered = _rotate(q, k, cos, sin)
    monkeypatch.undo()
    assert len(seen) == calls
    for got, want, x in zip(steered, plain, (q, k)):
        _assert_within_one_unit(got._data, want._data, x._data)


# --------------------------------------------------------------------------
# grouped matrix products (ISSUE 46: the held experts' three products)
# --------------------------------------------------------------------------
import functools

from paddle_tpu.ops.pallas import grouped_matmul as gmm

# rows a chunk, held experts, hidden width, an expert's width
SPARSE_CELLS = {"lfm2": (8192, 8, 2048, 1536),
                "kimi_linear": (8192, 8, 2304, 1024),
                "laguna": (16384, 32, 2048, 512),
                "moonlight": (8192, 8, 2048, 1408),
                "mellum2": (16384, 8, 2304, 896)}
# group sizes over 512 rows (4 row tiles of 128), and whether the rows
# past them hold NaN
GROUPINGS = {
    "tail_rows_hold_nan": ([100, 60, 130, 50], True),
    "empty_group_in_the_middle": ([200, 0, 0, 212], False),
    "one_group_holds_every_row": ([0, 512, 0, 0], False),
    "group_edges_inside_row_tiles": ([3, 250, 5, 254], False),
    "nothing_routed": ([0, 0, 0, 0], True),
    "edges_on_tile_edges_and_a_tail": ([128, 256, 0, 0], True),
}
PRODUCTS = ["result", "rows_gradient", "weights_gradient"]


@functools.lru_cache(maxsize=None)
def _grouped_products(dtype, m, k, n, sizes, nan, tiling=None):
    """(the kernels', ``ragged_dot``'s) result, rows' gradient and
    weights' gradient, the cotangent's rows past the last group zeroed
    for ``ragged_dot`` and, with ``nan``, NaN for the kernels, as are
    ``rows``' there."""
    dtype = jnp.dtype(dtype)
    kx, kw, kdy = jax.random.split(jax.random.PRNGKey(46), 3)
    x = jax.random.normal(kx, (m, k), dtype)
    dy = jax.random.normal(kdy, (m, n), dtype)
    w = jax.random.normal(kw, (len(sizes), k, n), dtype) * k ** -0.5
    groups = jnp.asarray(sizes, jnp.int32)
    routed = (jnp.arange(m) < sum(sizes))[:, None]
    want, back = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, groups), x, w)
    want = (want,) + back(jnp.where(routed, dy, 0))
    if nan:
        x, dy = jnp.where(routed, x, jnp.nan), jnp.where(routed, dy, jnp.nan)
    if tiling is None:
        got, back = jax.vjp(
            lambda x, w: gmm.grouped_dot(x, w, groups, interpret=True), x, w)
        got = (got,) + back(dy)
    else:
        by_rows, by_group = (
            gmm._plan(groups, m=m, tm=tiling[0], tail=tail)
            for tail in (True, False))
        got = (gmm._rows_call(x, w, by_rows, turned=False, interpret=True,
                              tiling=tiling),
               gmm._rows_call(dy, w, by_rows, turned=True, interpret=True,
                              tiling=tiling),
               gmm._weights_call(x, dy, by_group, interpret=True,
                                 tiling=tiling))
    return dict(zip(PRODUCTS, zip(got, want))), sum(sizes)


def _assert_products_agree(got, want):
    """To a unit in the last place of the largest value: the kernels
    and ``ragged_dot`` sum the same float32 products in another order
    and round once."""
    assert got.dtype == want.dtype and got.shape == want.shape
    unit = 2.0 ** -7 if got.dtype == jnp.bfloat16 else 1e-5
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= unit * max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("k,n", [(2304, 896), (2048, 1408), (2048, 512),
                                 (2048, 1536)])
def test_grouped_dot_is_ragged_dot_at_the_cells_widths(k, n, dtype, product):
    """The five cells' (hidden, expert) widths, 7 and 11 lane tiles of
    N among them, over 128 rows in 4 uneven groups that leave a tail."""
    pairs, routed = _grouped_products(dtype, 128, k, n, (30, 0, 51, 20),
                                      False)
    got, want = pairs[product]
    if product != "weights_gradient":
        want = want.at[routed:].set(0)
    _assert_products_agree(got, want)


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("grouping", list(GROUPINGS))
def test_grouped_dot_by_grouping(grouping, product):
    """Groups that sum to less than the rows (the rows past them read
    exactly 0 in the result and the rows' gradient and add 0 to the
    weights', NaN in ``rows`` and the cotangent there or not), an empty
    group in the middle, one group holding every row, group edges
    inside a row tile and on its edge."""
    sizes, nan = GROUPINGS[grouping]
    pairs, routed = _grouped_products("float32", 512, 128, 256, tuple(sizes),
                                      nan)
    got, want = pairs[product]
    if product != "weights_gradient":
        assert not np.asarray(got[routed:], np.float32).any()
        want = want.at[routed:].set(0)
    _assert_products_agree(got, want)
    if product == "weights_gradient":
        for g, size in enumerate(sizes):
            if not size:
                assert not np.asarray(got[g], np.float32).any()


@pytest.mark.parametrize("product", PRODUCTS)
def test_grouped_dot_sums_cut_widths_in_float32(product):
    """K and N in tiles of 128: the row kernels' accumulator over K
    tiles, each (group, K tile, N tile) of the weights' gradient its
    own sum over the group's row tiles."""
    pairs, routed = _grouped_products(
        "bfloat16", 512, 256, 384, (100, 60, 130, 50), True,
        (128, 128, 128))
    got, want = pairs[product]
    if product != "weights_gradient":
        want = want.at[routed:].set(0)
    _assert_products_agree(got, want)


@pytest.mark.parametrize("cell", list(SPARSE_CELLS))
def test_grouped_tiles_divide_the_cells_widths_and_fit_the_budget(cell):
    """The rule's ``tk`` and ``tn`` are multiples of 128 that divide the
    width they cut, a step's VMEM is within the stated budget, the row
    tile divides the chunk, and an expert width of 896 or 1408 is taken
    whole: the point of the kernels."""
    m, g, h, i = SPARSE_CELLS[cell]
    for k, n in ((h, i), (i, h)):
        assert gmm.takes(m, g, k, n)
        for kind in ("fwd", "dx", "dw"):
            tm, tk, tn = gmm.tiles(kind, m, g, k, n)
            cut_k, cut_n = (n, k) if kind == "dx" else (k, n)
            assert tm == 256
            assert cut_k % tk == 0 and tk % 128 == 0
            assert cut_n % tn == 0 and tn % 128 == 0
            assert gmm.vmem_bytes(kind, tm, tk, tn) <= gmm._BUDGET
            if i in (896, 1408):
                assert i in (tk, tn)
    assert not gmm.takes(80, 4, 32, 48)


def test_grouped_dot_names_its_kernels_and_refuses_mismatched_operands():
    x, w = _rand((256, 128)), _rand((2, 128, 256))
    groups = jnp.asarray([100, 100], jnp.int32)
    text = str(jax.make_jaxpr(lambda x, w, dy: jax.vjp(
        lambda x, w: gmm.grouped_dot(x, w, groups, interpret=True),
        x, w)[1](dy))(x, w, _rand((256, 256))))
    for name in ("grouped_matmul_fwd", "grouped_matmul_dx",
                 "grouped_matmul_dw"):
        assert text.count(f"name={name}") == 1
    with pytest.raises(ValueError, match="grouped_dot"):
        gmm.grouped_dot(x, w.astype(jnp.bfloat16), groups, interpret=True)
    with pytest.raises(ValueError, match="grouped_dot"):
        gmm.grouped_dot(x, w[:, :64], groups, interpret=True)


@pytest.mark.parametrize("width,taken", [(48, 0), (128, 1)])
def test_sparse_block_takes_the_kernels_in_a_tpu_program_alone(
        monkeypatch, width, taken):
    """``SparseMoEBlock`` chooses on the extents, the backend and
    whether a program is being captured: at multiples of 128 in a
    program captured on a TPU a chunk's three products go through
    ``grouped_dot``; at any other extent, off the TPU and in per-op
    dispatch they are ``jax.lax.ragged_dot``'s, and
    ``moe.grouped_kernel{layer}`` says which the captured program
    took."""
    import paddle_tpu as paddle
    from paddle_tpu.core import scope
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    layer = f"kernel_choice_{width}"
    # the block's ring and gauges outlive it: other files' tests, in
    # the same process, read every layer's
    reg = metrics.registry()
    monkeypatch.setattr(moe, "_calls_of", dict(moe._calls_of))
    monkeypatch.setattr(reg, "_metrics", dict(reg._metrics))
    block = moe.SparseMoEBlock(128, width, 8, 2, expert_offset=2,
                               experts_held=4, name=layer)
    x = paddle.Tensor(_rand((128, 128), seed=5))

    def gauge():
        return metrics.snapshot()["moe"]["grouped_kernel"][f"layer={layer}"]

    seen, real = [], gmm.grouped_dot
    monkeypatch.setattr(gmm, "grouped_dot", lambda r, *a, **kw: seen.append(
        r.shape) or real(r, *a, **kw, interpret=True))
    plain = block(x)[0]
    with scope.capture():               # captured, off the TPU
        block(x)
    assert gauge() == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    block(x)                            # on it, per-op dispatch
    assert not seen and gauge() == 0
    with scope.capture():
        steered = block(x)[0]
    assert len(seen) == 3 * taken and gauge() == taken
    monkeypatch.undo()
    np.testing.assert_allclose(steered._data, plain._data, atol=1e-5,
                               rtol=1e-5)


# --------------------------------------------------------------------------
# ragged paged attention (ISSUE 3: multi-page compacted-grid serving kernel)
# --------------------------------------------------------------------------
from paddle_tpu.ops.pallas import paged_attention as pga


def _paged_gather(pool, bt, b, length, ps):
    """[L, Hk, D] kv of sequence ``b`` out of the page pool."""
    return np.stack([np.asarray(pool)[:, bt[b, t // ps], t % ps]
                     for t in range(length)], 0)


def _ref_causal_offset(q, k, v, kv_len, q_len):
    """Dense reference with the ragged causal rule: q token i attends
    kv positions <= kv_len - q_len + i.  q [q_len, Hq, D]; k/v
    [kv_len, Hk, D]."""
    hq, hk = q.shape[1], k.shape[1]
    kt = np.repeat(k, hq // hk, axis=1)
    vt = np.repeat(v, hq // hk, axis=1)
    s = np.einsum("qhd,lhd->hql", q, kt) / np.sqrt(q.shape[-1])
    qpos = kv_len - q_len + np.arange(q_len)
    mask = np.arange(kv_len)[None, :] <= qpos[:, None]
    s = np.where(mask[None], s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return np.einsum("hql,lhd->qhd", p, vt)


def _paged_setup(rng, lens, hk, ps, d, extra_pages=3):
    """Page pools with SHUFFLED page assignment (block-table indirection
    must matter) + block tables; page 0 left unassigned (null page)."""
    B = len(lens)
    NP = -(-max(lens) // ps) + 1
    total = B * NP + extra_pages
    pk = rng.normal(size=(hk, total, ps, d)).astype(np.float32)
    pv = rng.normal(size=(hk, total, ps, d)).astype(np.float32)
    ids = np.arange(1, total)
    rng.shuffle(ids)
    bt = np.zeros((B, NP), np.int32)
    n = 0
    for b in range(B):
        need = -(-lens[b] // ps)
        bt[b, :need] = ids[n:n + need]
        n += need
    return pk, pv, bt


@pytest.mark.parametrize("hq,hk,ps,lens,ppb", [
    (4, 4, 8, [5, 16, 23], 1),     # rep 1, non-aligned lengths
    (8, 2, 16, [1, 30, 17], 2),    # GQA rep 4, len < page, multi-page
    (6, 3, 8, [9, 40], 4),         # GQA rep 2, ppb > pages of some seq
    (8, 8, 16, [33], 3),           # ppb not dividing the page count
])
def test_paged_decode_matches_dense(hq, hk, ps, lens, ppb):
    rng = np.random.default_rng(0)
    d = 32
    B = len(lens)
    pk, pv, bt = _paged_setup(rng, lens, hk, ps, d)
    q = rng.normal(size=(B, hq, d)).astype(np.float32)
    out = np.asarray(pga.paged_decode_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(bt), jnp.asarray(lens, dtype=jnp.int32),
        interpret=True, pages_per_block=ppb))
    for b in range(B):
        ref = _ref_causal_offset(
            q[b][None], _paged_gather(pk, bt, b, lens[b], ps),
            _paged_gather(pv, bt, b, lens[b], ps), lens[b], 1)[0]
        np.testing.assert_allclose(out[b], ref, atol=2e-5, rtol=2e-5)


def test_paged_decode_traced_lengths_no_recompile():
    """seq_lens/block_tables ride the scalar-prefetch channel: one
    compiled program serves CHANGING lengths and re-pointed tables (the
    serving engine's admission/retirement contract)."""
    rng = np.random.default_rng(1)
    hq = hk = 2
    ps, d, B, NP = 8, 16, 2, 3
    pk, pv, bt = _paged_setup(rng, [20, 11], hk, ps, d)
    q = rng.normal(size=(B, hq, d)).astype(np.float32)

    traces = []

    @jax.jit
    def step(q, pk, pv, bt, lens):
        traces.append(1)
        return pga.paged_decode_attention(q, pk, pv, bt, lens,
                                          interpret=True,
                                          pages_per_block=2)

    for lens in ([20, 11], [7, 23], [1, 1]):
        out = np.asarray(step(jnp.asarray(q), jnp.asarray(pk),
                              jnp.asarray(pv), jnp.asarray(bt),
                              jnp.asarray(lens, dtype=jnp.int32)))
        for b in range(B):
            ref = _ref_causal_offset(
                q[b][None], _paged_gather(pk, bt, b, lens[b], ps),
                _paged_gather(pv, bt, b, lens[b], ps), lens[b], 1)[0]
            np.testing.assert_allclose(out[b], ref, atol=2e-5,
                                       rtol=2e-5)
    assert len(traces) == 1  # lengths are data, not shape


@pytest.mark.parametrize("qb", [2, 4])
def test_ragged_mixed_prefill_decode(qb):
    """One kernel call serving a continuously-batched step: prefill
    chunks (q_len > 1) and decodes (q_len 1) with non-page-aligned
    lengths, causal offsets per sequence."""
    rng = np.random.default_rng(2)
    hq, hk, ps, d, ppb = 4, 2, 8, 16, 2
    kv_lens = [13, 6, 21, 1]
    q_lens = [5, 1, 9, 1]          # mixed prefill + decode
    B = len(kv_lens)
    pk, pv, bt = _paged_setup(rng, kv_lens, hk, ps, d)
    segs = [-(-ql // qb) * qb for ql in q_lens]
    starts = np.cumsum([0] + segs[:-1])
    T = sum(segs)
    q = np.zeros((T, hq, d), np.float32)
    for b in range(B):
        q[starts[b]:starts[b] + q_lens[b]] = rng.normal(
            size=(q_lens[b], hq, d))
    out = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(bt), jnp.asarray(kv_lens, dtype=jnp.int32),
        jnp.asarray(q_lens, dtype=jnp.int32), q_block=qb,
        pages_per_block=ppb, interpret=True))
    for b in range(B):
        ref = _ref_causal_offset(
            q[starts[b]:starts[b] + q_lens[b]],
            _paged_gather(pk, bt, b, kv_lens[b], ps),
            _paged_gather(pv, bt, b, kv_lens[b], ps),
            kv_lens[b], q_lens[b])
        np.testing.assert_allclose(out[starts[b]:starts[b] + q_lens[b]],
                                   ref, atol=2e-5, rtol=2e-5)


def test_ragged_zero_qlen_sits_out():
    """q_len 0 (a slot sitting a step out) contributes no work items and
    corrupts nothing."""
    rng = np.random.default_rng(3)
    hq = hk = 2
    ps, d, qb = 8, 16, 2
    kv_lens = [10, 9]
    q_lens = [2, 0]
    pk, pv, bt = _paged_setup(rng, kv_lens, hk, ps, d)
    q = np.zeros((2, hq, d), np.float32)
    q[:2] = rng.normal(size=(2, hq, d))
    out = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
        jnp.asarray(bt), jnp.asarray(kv_lens, dtype=jnp.int32),
        jnp.asarray(q_lens, dtype=jnp.int32), q_block=qb,
        pages_per_block=2, interpret=True))
    ref = _ref_causal_offset(q[:2], _paged_gather(pk, bt, 0, 10, ps),
                             _paged_gather(pv, bt, 0, 10, ps), 10, 2)
    np.testing.assert_allclose(out[:2], ref, atol=2e-5, rtol=2e-5)


def test_pages_per_block_heuristic_and_candidates():
    from paddle_tpu.ops.pallas.paged_attention import (
        _tune_candidates, default_pages_per_block)
    assert default_pages_per_block(16, 128, 64) == 32   # 512-token target
    assert default_pages_per_block(16, 2, 64) == 2      # capped by table
    cands = _tune_candidates(16, 128, 64)
    assert cands[0] == 1 and all(b == a * 2 for a, b in
                                 zip(cands, cands[1:]))


# --------------------------------------------------------------------------
# int8 KV pages with in-kernel dequant (ISSUE 7)
# --------------------------------------------------------------------------

def _quant_pools(rng, lens, hk, ps, d):
    """Shuffled-page pools like ``_paged_setup``, plus their int8
    quantization (``quantization.kv_quantize``)."""
    from paddle_tpu.quantization import kv_quantize

    pk, pv, bt = _paged_setup(rng, lens, hk, ps, d)
    qk, sk = kv_quantize(jnp.asarray(pk))
    qv, sv = kv_quantize(jnp.asarray(pv))
    return pk, pv, bt, qk, sk, qv, sv


@pytest.mark.parametrize("hq,hk,ps,lens,q_lens,ppb", [
    (4, 2, 8, [13, 6, 21, 1], [5, 1, 9, 1], 2),  # mixed prefill+decode
    (8, 2, 16, [1, 30, 17], [1, 1, 1], 2),       # GQA decode
])
def test_ragged_int8_kernel_bitwise_vs_dequant(hq, hk, ps, lens,
                                               q_lens, ppb):
    """The quant kernel's contract: int8 pages + per-slot scales through
    the in-DMA dequant must be BITWISE what the fp kernel computes on
    the dequantized pools (same f32 values entering the same flash
    recurrence), and within int8 error of the original fp pools."""
    from paddle_tpu.quantization import kv_dequantize

    rng = np.random.default_rng(5)
    d, qb = 16, 2
    B = len(lens)
    pk, pv, bt, qk, sk, qv, sv = _quant_pools(rng, lens, hk, ps, d)
    segs = [-(-ql // qb) * qb for ql in q_lens]
    starts = np.cumsum([0] + segs[:-1])
    q = np.zeros((sum(segs), hq, d), np.float32)
    for b in range(B):
        q[starts[b]:starts[b] + q_lens[b]] = rng.normal(
            size=(q_lens[b], hq, d))
    args = (jnp.asarray(bt), jnp.asarray(lens, dtype=jnp.int32),
            jnp.asarray(q_lens, dtype=jnp.int32))
    out_q = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), qk, qv, *args, q_block=qb, pages_per_block=ppb,
        interpret=True, k_scales=sk, v_scales=sv))
    out_deq = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), kv_dequantize(qk, sk), kv_dequantize(qv, sv),
        *args, q_block=qb, pages_per_block=ppb, interpret=True))
    out_fp = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), *args,
        q_block=qb, pages_per_block=ppb, interpret=True))
    rows = np.concatenate([np.arange(starts[b], starts[b] + q_lens[b])
                           for b in range(B)])
    np.testing.assert_array_equal(out_q[rows], out_deq[rows])  # bitwise
    # int8 absmax per-vector: softmax-weighted values stay close
    np.testing.assert_allclose(out_q[rows], out_fp[rows], atol=0.05,
                               rtol=0.05)


def test_ragged_int8_exact_grid_is_bitwise_vs_fp():
    """KV values on the int8 grid (v = n * s with s an exact binary
    scale) quantize losslessly, so the QUANT kernel must reproduce the
    FP kernel's output bit for bit — pinning that the dequant multiply
    sits before the dots exactly where the fp path casts."""
    rng = np.random.default_rng(9)
    hq = hk = 2
    ps, d, qb, ppb = 8, 16, 2, 2
    lens, q_lens = [11, 7], [3, 1]
    s = 2.0 ** -5                       # exact in fp32
    B = len(lens)
    NP = -(-max(lens) // ps) + 1
    total = B * NP + 2
    ints = rng.integers(-127, 128, size=(hk, total, ps, d))
    ints2 = rng.integers(-127, 128, size=(hk, total, ps, d))
    # pin every vector's absmax at 127 so kv_quantize's scale is
    # EXACTLY s (127*s/127) and the int8 roundtrip is lossless
    ints[..., 0] = 127
    ints2[..., 0] = -127
    pk = (ints * s).astype(np.float32)
    pv = (ints2 * s).astype(np.float32)
    from paddle_tpu.quantization import kv_quantize
    qk, sk = kv_quantize(jnp.asarray(pk))
    qv, sv = kv_quantize(jnp.asarray(pv))
    np.testing.assert_array_equal(
        np.asarray(qk, np.float32) * np.asarray(sk)[..., None], pk)
    bt = np.zeros((B, NP), np.int32)
    ids = np.arange(1, total)
    rng.shuffle(ids)
    n = 0
    for b in range(B):
        need = -(-lens[b] // ps)
        bt[b, :need] = ids[n:n + need]
        n += need
    segs = [-(-ql // qb) * qb for ql in q_lens]
    starts = np.cumsum([0] + segs[:-1])
    q = rng.normal(size=(sum(segs), hq, d)).astype(np.float32)
    args = (jnp.asarray(bt), jnp.asarray(lens, dtype=jnp.int32),
            jnp.asarray(q_lens, dtype=jnp.int32))
    out_q = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), qk, qv, *args, q_block=qb, pages_per_block=ppb,
        interpret=True, k_scales=sk, v_scales=sv))
    out_fp = np.asarray(pga.ragged_paged_attention(
        jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv), *args,
        q_block=qb, pages_per_block=ppb, interpret=True))
    rows = np.concatenate([np.arange(starts[b], starts[b] + q_lens[b])
                           for b in range(len(lens))])
    np.testing.assert_array_equal(out_q[rows], out_fp[rows])


def test_ragged_int8_requires_both_scales():
    rng = np.random.default_rng(1)
    pk, pv, bt, qk, sk, qv, sv = _quant_pools(rng, [9], 2, 8, 16)
    with pytest.raises(ValueError, match="both"):
        pga.ragged_paged_attention(
            jnp.asarray(rng.normal(size=(2, 2, 16)), jnp.float32),
            qk, qv, jnp.asarray(bt), jnp.asarray([9], dtype=jnp.int32),
            jnp.asarray([2], dtype=jnp.int32), q_block=2,
            pages_per_block=1, interpret=True, k_scales=sk)
