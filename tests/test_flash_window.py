"""The sliding window in the flash kernel pair
(``ops/pallas/flash_attention.py``, ``window=``): the kernels in
interpret mode and the twin ``flash_attention_bwd_jnp`` against a
float32 masked softmax built from positions, forward and all three
gradients; a window no shorter than the keys against the plain causal
call, bit for bit and program for program; the ``flash.tiles`` gauges
against a brute-force count of the tiles outside the band; and the
window in the autotune key and the gauges' label.

Model-free, tiny shapes: about 40 s under the tier-1 command.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.ops.pallas import flash_attention as fa

TOL = 2e-5      # float32 on both sides, other orders of summation


def _rand(shape, dtype=jnp.float32, seed=0, scale=0.5):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape) * scale, dtype)


def _inputs(b, sq, sk, hq, hk, d, dtype=jnp.float32):
    return (_rand((b, sq, hq, d), dtype, 1), _rand((b, sk, hk, d), dtype, 2),
            _rand((b, sk, hk, d), dtype, 3),
            _rand((b, sq, hq, d), jnp.float32, 4))


def _keep(sq, sk, window):
    """[sq, sk] bool from positions: key ``j`` is seen by query ``i``
    (counted from the end of the keys) where ``j <= i`` and ``i - j <
    window``."""
    i = np.arange(sq)[:, None] + (sk - sq)
    j = np.arange(sk)[None, :]
    return (j <= i) & ((i - j < window) if window is not None else True)


def _masked_softmax(q, k, v, window):
    """Float32 masked softmax attention, scores materialised."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        / math.sqrt(q.shape[-1])
    keep = jnp.asarray(_keep(q.shape[1], k.shape[1], window))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1.0)


def _kernel_and_twin(q, k, v, w, window, blocks, bwd_blocks):
    """(o, (dq, dk, dv)) of the kernels in interpret mode, and the
    twin's three gradients from the kernels' own residuals."""
    def loss(q, k, v):
        return (fa.flash_attention(
            q, k, v, causal=True, window=window, interpret=True,
            blocks=blocks, bwd_blocks=bwd_blocks).astype(jnp.float32)
            * w).sum()

    o = fa.flash_attention(q, k, v, causal=True, window=window,
                           interpret=True, blocks=blocks)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    scale = 1.0 / math.sqrt(q.shape[-1])
    qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    _, lse = fa._fwd(qt, kt, vt, None, None, scale, True, True, blocks,
                     fa._window(window, True, k.shape[1]))
    twin = fa.flash_attention_bwd_jnp(
        q, k, v, w.astype(q.dtype), o, lse, causal=True, blocks=bwd_blocks,
        window=window)
    return o, grads, twin


# (sq, sk, hq, hk, window, forward blocks, backward blocks): 16-wide
# blocks in rows of 64, so 15 / 16 / 17 lie below, at and above a block
# edge and 31 / 32 / 33 round the second one
_CASES = [
    pytest.param(64, 64, 1, 1, 1, (16, 16), (16, 16), id="w1-itself-alone"),
    pytest.param(64, 64, 1, 1, 15, (16, 16), (16, 16), id="w15-below-edge"),
    pytest.param(64, 64, 1, 1, 16, (16, 16), (16, 16), id="w16-at-edge"),
    pytest.param(64, 64, 1, 1, 17, (16, 16), (16, 16), id="w17-above-edge"),
    pytest.param(64, 64, 1, 1, 33, (32, 16), (16, 32), id="w33-two-shapes"),
    pytest.param(64, 64, 4, 1, 24, (16, 32), (32, 16), id="gqa-4-to-1"),
    pytest.param(50, 50, 2, 1, 9, (16, 16), (16, 16), id="gqa-padded-tail"),
    pytest.param(40, 64, 1, 1, 24, (16, 16), (16, 16), id="keys-longer"),
    # Laguna's window of 512 keys at the blocks the chip was read at,
    # a group of 6 (its full layers' 48 / 8, here under a window) and of
    # 8 (its window layers' 64 / 8): at 512 x 512 an edge cuts every
    # tile, at 256 x 256 one tile in three is whole
    pytest.param(1024, 1024, 6, 1, 512, (512, 512), (512, 512),
                 id="w512-blocks-512-group-6"),
    pytest.param(1024, 1024, 8, 1, 512, (256, 256), (256, 256),
                 id="w512-blocks-256-group-8"),
    pytest.param(1024, 1024, 8, 1, 512, (256, 512), (512, 256),
                 id="w512-unequal-group-8"),
]


@pytest.mark.parametrize("sq,sk,hq,hk,window,blocks,bwd_blocks", _CASES)
def test_kernels_and_twin_against_a_float32_masked_softmax(
        sq, sk, hq, hk, window, blocks, bwd_blocks):
    q, k, v, w = _inputs(1, sq, sk, hq, hk, 16)
    o, grads, twin = _kernel_and_twin(q, k, v, w, window, blocks,
                                      bwd_blocks)
    want_o = _masked_softmax(q, k, v, window)
    want = jax.grad(lambda q, k, v: (_masked_softmax(q, k, v, window)
                                     * w).sum(), argnums=(0, 1, 2))(q, k, v)
    _close(o, want_o)
    for name, got, tw, ref in zip(("dq", "dk", "dv"), grads, twin, want):
        _close(got, ref)
        # the twin replays the kernel's walk: bit for bit
        assert np.array_equal(np.asarray(got), np.asarray(tw)), name


def test_bfloat16_gqa_window_is_bitwise_the_twin_and_near_float32():
    q, k, v, w = _inputs(1, 96, 96, 2, 1, 32, jnp.bfloat16)
    o, grads, twin = _kernel_and_twin(q, k, v, w, 40, (32, 32), (32, 32))
    assert o.dtype == jnp.bfloat16
    _close(o, _masked_softmax(q, k, v, 40), tol=2e-2)
    for got, tw in zip(grads, twin):
        assert got.dtype == tw.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(tw, np.float32))


@pytest.mark.parametrize("window", [64, 65, 1000])
def test_a_window_no_shorter_than_the_keys_is_the_causal_call(window):
    """Bit for bit, and the very program: the same jaxpr as a call that
    names no window, the plain kernels' names in it."""
    q, k, v, w = _inputs(1, 64, 64, 4, 2, 16)

    def grads(**kw):
        def loss(q, k, v):
            return (fa.flash_attention(
                q, k, v, causal=True, interpret=True, blocks=(16, 16),
                bwd_blocks=(32, 32), **kw).astype(jnp.float32) * w).sum()
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    plain, windowed = grads(), grads(window=window)
    for a, b in zip(jax.tree.leaves(plain(q, k, v)),
                    jax.tree.leaves(windowed(q, k, v))):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = str(jax.make_jaxpr(windowed)(q, k, v))
    assert text == str(jax.make_jaxpr(plain)(q, k, v))
    assert text == str(jax.make_jaxpr(grads(window=None))(q, k, v))
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert "flash_window" not in text
    # one key short of the row it is another program, under other names
    text = str(jax.make_jaxpr(grads(window=63))(q, k, v))
    assert "flash_window_fwd" in text and "flash_window_bwd" in text
    assert "flash_attention_fwd" not in text
    assert "flash_attention_bwd" not in text


def test_the_fallback_and_the_functional_take_the_same_window():
    """``F.scaled_dot_product_attention(window=)`` reaches the kernels
    (``backend="pallas"``) and the XLA fallback alike."""
    import paddle_tpu as paddle
    q, k, v, _ = _inputs(1, 48, 48, 4, 2, 16)
    want = _masked_softmax(q, k, v, 10)
    for backend in ("xla", "pallas"):
        got = F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            is_causal=True, window=10, backend=backend)
        _close(got._read(), want)
    with pytest.raises(ValueError, match="is_causal"):
        F.scaled_dot_product_attention(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            window=10)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, window=10, interpret=True)
    with pytest.raises(ValueError, match="window of 0"):
        fa.flash_attention(q, k, v, causal=True, window=0, interpret=True)


def _count_tiles(kernel, sq, sk, window, blocks):
    """Brute force over the elements: a tile is skipped when none of its
    elements lies in the band; the backward runs a tile plain when all
    of them do and all are in range, masked otherwise; a causal
    forward's one body is masked."""
    bq, bk = blocks
    nq, nk = -(-sq // bq), -(-sk // bk)
    rows = np.arange(nq * bq)[:, None] + (sk - sq)
    cols = np.arange(nk * bk)[None, :]
    band = (cols <= rows) & (rows - cols < window)
    inside = (cols < sk) & (np.arange(nq * bq)[:, None] < sq)
    n = {"plain": 0, "masked": 0, "skipped": 0}
    for iq in range(nq):
        for ik in range(nk):
            t = np.s_[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            if not band[t].any():
                n["skipped"] += 1
            elif kernel == "bwd" and (band[t] & inside[t]).all():
                n["plain"] += 1
            else:
                n["masked"] += 1
    return n


@pytest.mark.parametrize("sq,sk,window,blocks", [
    pytest.param(128, 128, 40, (16, 16), id="band-of-4-blocks"),
    pytest.param(128, 128, 16, (16, 16), id="window-is-a-block"),
    pytest.param(128, 128, 33, (32, 16), id="unequal-blocks"),
    pytest.param(100, 100, 24, (16, 32), id="padded"),
    pytest.param(64, 128, 48, (16, 16), id="keys-longer"),
])
def test_tile_gauges_skip_exactly_the_tiles_outside_the_band(
        sq, sk, window, blocks):
    """``flash.tiles``' ``skipped`` counts the tiles above the diagonal
    AND those behind the window, its ``shape`` label names the window,
    and the forward's band walk and the backward's shortened grid visit
    the rest."""
    from paddle_tpu.observability import metrics
    hq = 2
    q, k, v, w = _inputs(1, sq, sk, hq, 1, 8)
    jax.grad(lambda a: (fa.flash_attention(
        a, k, v, causal=True, window=window, interpret=True, blocks=blocks,
        bwd_blocks=blocks).astype(jnp.float32) * w).sum())(q)
    shape = f"b1h{hq}sq{sq}sk{sk}d8c1w{window}s0.{blocks[0]}x{blocks[1]}"
    for kernel in ("fwd", "bwd"):
        want = _count_tiles(kernel, sq, sk, window, blocks)
        got = {kind: metrics.registry().gauge(
            "flash.tiles", labels={"kernel": kernel, "kind": kind,
                                   "shape": shape}).value / hq
            for kind in want}
        assert got == want, (kernel, got, want)
        # most of a long row's tiles lie behind the window
        assert want["skipped"] > want["masked"] + want["plain"] or sq < 128
    # the backward's grid holds the band's q blocks, not the row's
    bq, bk = blocks
    steps = fa._bwd_q_steps(window=window, sq=sq, sk=sk, bq=bq, bk=bk)
    visited = np.zeros((-(-sk // bk), -(-sq // bq)), bool)
    for ik in range(visited.shape[0]):
        first = int(fa._bwd_q_first(np.asarray(ik), sq=sq, sk=sk, bq=bq,
                                    bk=bk))
        visited[ik, first:first + steps] = True
    plain, masked = fa._bwd_tile_kinds(
        np.arange(visited.shape[0])[:, None],
        np.arange(visited.shape[1])[None, :], causal=True, has_seg=False,
        sq=sq, sk=sk, bq=bq, bk=bk, window=window)
    assert not ((plain | masked) & ~visited).any()
    assert steps <= min((bk + window - 2) // bq + 2, visited.shape[1])
    assert steps < visited.shape[1] or sq < sk


@pytest.mark.parametrize("heads,kv,window,blocks,plain,visited", [
    # a q block's band of 1,023 keys touches exactly two key blocks of
    # 512 and an edge crosses both: 31 tiles a head, none whole
    pytest.param(64, 8, 512, (512, 512), 0, 31, id="w512-at-512-none-whole"),
    # three tiles of 256 a q block, the middle one whole
    pytest.param(64, 8, 512, (256, 256), 31, 93, id="w512-at-256"),
    # Mellum2's window of 1,024: three tiles of 512, the middle one whole
    pytest.param(32, 4, 1024, (512, 512), 15, 45, id="w1024-at-512"),
])
def test_the_gauges_of_a_traced_call_at_the_benchmarks_shapes(
        heads, kv, window, blocks, plain, visited):
    """``flash.tiles`` is set while a call is traced, so the shapes of
    the chip are read here with nothing run: the window layer's call at
    8,192 positions, by its label (heads and window in it)."""
    from paddle_tpu.observability import metrics
    q = jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8192, kv, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda a, b, c: fa.flash_attention(
        a, b, c, causal=True, window=window, interpret=True, blocks=blocks,
        bwd_blocks=blocks).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        q, k, k)
    shape = (f"b1h{heads}sq8192sk8192d128c1w{window}s0."
             f"{blocks[0]}x{blocks[1]}")
    got = {(kernel, kind): metrics.registry().gauge(
        "flash.tiles", labels={"kernel": kernel, "kind": kind,
                               "shape": shape}).value / heads
        for kernel in ("fwd", "bwd") for kind in ("plain", "masked",
                                                  "skipped")}
    tiles = (8192 // blocks[0]) * (8192 // blocks[1])
    assert got["bwd", "plain"] == plain
    assert got["bwd", "masked"] == visited - plain
    assert got["bwd", "skipped"] == got["fwd", "skipped"] == tiles - visited
    assert (got["fwd", "plain"], got["fwd", "masked"]) == (0, visited)
    # the score pairs the kernels visit against the band's
    band = window * 8192 - window * (window - 1) // 2
    ratio = visited * blocks[0] * blocks[1] / band
    assert ratio == pytest.approx(
        {(512, 512): 2.0, (512, 256): 1.5, (1024, 512): 1.5}[
            window, blocks[0]], abs=0.01)


def test_the_window_paths_default_blocks_are_one_pair_at_both_windows():
    """The chip's readings at 1,024 keys (PR 42) and at 512 (PR 44)
    chose one pair, 512 x 512 forward and backward
    (``_window_block_sizes``): Mellum2's calls take what they took."""
    for window in (1024, 512, 128):
        assert fa._block_sizes(8192, 8192, True, window) == (512, 512)
        assert fa._bwd_block_sizes(8192, 8192, True, window) == (512, 512)
    assert fa._window_block_sizes(8192, 8192) == ((512, 512), (512, 512))
    # a row shorter than a block is one block
    assert fa._window_block_sizes(64, 64) == ((64, 64), (64, 64))
    # the plain call's defaults do not move
    assert fa._block_sizes(8192, 8192, True) == (512, 512)
    assert fa._bwd_block_sizes(8192, 8192, True) == (1024, 1024)


def test_a_windowed_and_a_plain_call_share_no_tuned_entry_and_no_series():
    """The window is in the autotune signature and in the gauges'
    ``shape`` label, a window the kernels never see (none, or no shorter
    than the keys) is not."""
    from paddle_tpu.observability import metrics
    plain = fa._shape_sig((1, 32, 8192, 128), 8192, True)
    assert plain == "b1h32sq8192sk8192d128c1"
    assert fa._shape_sig((1, 32, 8192, 128), 8192, True, 128, None) == plain
    banded = fa._shape_sig((1, 32, 8192, 128), 8192, True, 128, 1024)
    assert banded == plain + "w1024"
    assert fa._shape_sig((1, 16, 64, 192), 64, True, 128, 8) \
        == "b1h16sq64sk64d192v128c1w8"
    assert fa._window(8192, True, 8192) is None
    assert fa._window(9000, True, 8192) is None
    assert fa._window(8191, True, 8192) == 8191
    # the autotune probe keys its cache by the signature it is handed
    seen = []

    class Cache(dict):
        def get(self, key, default=None):
            seen.append(key)
            return default

    from paddle_tpu.ops.pallas import autotune as at
    real = at._load_cache
    at._load_cache = lambda: Cache()
    try:
        q = jax.ShapeDtypeStruct((1, 4, 256, 16), jnp.float32)
        jax.eval_shape(lambda a: (fa._autotuned_blocks(
            a, a, 0.25, True, a, 24), fa._autotuned_blocks(
                a, a, 0.25, True, a))[0] or 0, q)
    finally:
        at._load_cache = real
    assert [key.split("|")[-1] for key in seen] == [
        "b1h4sq256sk256d16c1w24", "b1h4sq256sk256d16c1"]
    # and the two calls' gauges are two series
    q, k, v, _ = _inputs(1, 64, 64, 2, 2, 8)
    for window in (None, 24):
        fa.flash_attention(q, k, v, causal=True, window=window,
                           interpret=True, blocks=(16, 16))
    reg = metrics.registry()
    series = {w: reg.gauge("flash.tiles", labels={
        "kernel": "fwd", "kind": "skipped",
        "shape": f"b1h2sq64sk64d8c1{w}s0.16x16"}).value
        for w in ("", "w24")}
    # 6 tiles above the diagonal; behind a window of 24 one more, (3, 0)
    assert series == {"": 2 * 6, "w24": 2 * 7}
