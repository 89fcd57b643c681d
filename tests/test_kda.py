"""``F.kda_chunk`` (``ops/pallas/kda.py``): the chunked gated delta rule
with a per-channel decay against the token-by-token recurrence, in
float32 on the CPU: the result and every gradient (q, k, v, g, beta).

Tolerance.  Both sides are float32 in different orders of summation
(the chunked form sums a chunk's corrections through a triangular
inverse): 2e-5 relative to the largest entry holds at ordinary decays
(observed at most 2e-6), and would not hold one bfloat16 operand
(2^-8).  With ``g`` down to -20 a step nearly every entry is a
difference of products of e^-20's and the float32 recurrence itself is
good to about 1e-5 of the largest: the float64 recurrence is the
witness there, at 5e-5.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.ops.pallas import kda as K  # noqa: E402

TOL = 2e-5


def recurrence(q, k, v, g, beta):
    """The recurrence of ``ops/pallas/kda.py``'s head, a token at a
    time, in numpy float64: the test's own witness."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, s, h, dk = q.shape

    def l2(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + K.L2_EPS)

    q, k = l2(q) / np.sqrt(dk), l2(k)
    state = np.zeros((b, h, dk, v.shape[-1]))
    out = np.zeros(v.shape)
    for t in range(s):
        state = state * np.exp(g[:, t])[..., None]
        seen = (state * k[:, t][..., None]).sum(-2)
        state = state + k[:, t][..., None] * (
            beta[:, t][..., None] * (v[:, t] - seen))[..., None, :]
        out[:, t] = (state * q[:, t][..., None]).sum(-2)
    return out


def recurrence_jax(q, k, v, g, beta):
    """The same recurrence as a scan, for its gradients."""
    dk = q.shape[-1]

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + K.L2_EPS)

    q, k = l2(q) / np.sqrt(dk), l2(k)

    def token(state, xs):
        qt, kt, vt, gt, bt = xs
        state = state * jnp.exp(gt)[..., None]
        seen = jnp.sum(state * kt[..., None], axis=-2)
        state = state + kt[..., None] * (
            bt[..., None] * (vt - seen))[..., None, :]
        return state, jnp.sum(state * qt[..., None], axis=-2)

    b, s, h, _ = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), q.dtype),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def operands(s, dk=16, dv=16, g_min=-1.0, seed=0, heads=2, dtype="f4"):
    rng = np.random.default_rng(seed)
    shape = (2, s, heads)
    q, k = (rng.standard_normal((*shape, dk)) for _ in range(2))
    v, w = (rng.standard_normal((*shape, dv)) for _ in range(2))
    g = g_min * rng.random((*shape, dk))
    beta = 1 / (1 + np.exp(-rng.standard_normal(shape)))
    return tuple(jnp.asarray(x, dtype) for x in (q, k, v, g, beta)), \
        jnp.asarray(w, dtype)


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.isfinite(got).all()
    gap = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert gap <= tol, gap


def value_and_grads(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2, 3, 4)))(*args)


# chunk edges inside the row, a row that is no multiple of the chunk, a
# row shorter than one chunk; one, two and four sub-blocks a chunk
@pytest.mark.parametrize("chunk,s", [(16, 40), (32, 20), (64, 100)])
def test_chunked_against_the_recurrence_forward_and_every_gradient(chunk, s):
    args, w = operands(s)
    out = jax.jit(lambda *a: K.kda_chunk(*a, chunk=chunk, how="xla"))(*args)
    assert out.shape == args[2].shape and out.dtype == jnp.float32
    close(out, recurrence(*args))
    _, got = value_and_grads(
        lambda *a: K.kda_chunk(*a, chunk=chunk, how="xla"), args, w)
    _, want = value_and_grads(recurrence_jax, args, w)
    for g, r, like in zip(got, want, args):
        assert g.shape == like.shape
        close(g, r)


def test_a_strong_decay_is_finite_and_equal():
    """``g`` down to -20 a step: e^-G over a chunk would be e^1280; the
    scores are made about reference points and nothing overflows."""
    args, w = operands(48, g_min=-20.0, seed=1)
    out = jax.jit(lambda *a: K.kda_chunk(*a, chunk=32, how="xla"))(*args)
    close(out, recurrence(*args), tol=5e-5)
    _, got = value_and_grads(
        lambda *a: K.kda_chunk(*a, chunk=32, how="xla"), args, w)
    with jax.enable_x64():
        args64 = tuple(jnp.asarray(np.asarray(a), jnp.float64) for a in args)
        _, want = value_and_grads(
            recurrence_jax, args64, jnp.asarray(np.asarray(w), jnp.float64))
    for g, r in zip(got, want):
        close(g, r, tol=5e-5)


def test_a_decay_of_one_and_unequal_widths():
    """g = 0 is the plain delta rule; the values' width is its own."""
    (q, k, v, g, beta), _ = operands(40, dk=16, dv=32, seed=2)
    out = K.kda_chunk(q, k, v, 0 * g, beta, chunk=16, how="xla")
    close(out, recurrence(q, k, v, 0 * g, beta))


@pytest.mark.parametrize("chunk,s,g_min", [(32, 40, -1.0), (16, 24, -20.0)])
def test_the_kernels_in_interpret_mode_are_the_xla_form(chunk, s, g_min):
    """One algebra, two executions: the Pallas kernels' grid, block
    windows, reversed backward sweep and carried state against the
    scan, forward and every gradient."""
    args, w = operands(s, g_min=g_min, seed=3)
    fwd = {how: jax.jit(lambda *a, how=how: K.kda_chunk(
        *a, chunk=chunk, how=how))(*args) for how in ("interpret", "xla")}
    close(fwd["interpret"], fwd["xla"], tol=1e-6)
    grads = {how: value_and_grads(lambda *a, how=how: K.kda_chunk(
        *a, chunk=chunk, how=how), args, w)[1] for how in ("interpret", "xla")}
    for a, b in zip(grads["interpret"], grads["xla"]):
        close(a, b, tol=1e-6)


def test_bfloat16_operands_give_a_bfloat16_result_near_the_float32():
    args, _ = operands(64, seed=4)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    out = jax.jit(lambda *a: K.kda_chunk(*a, chunk=32, how="xla"))(*low)
    assert out.dtype == jnp.bfloat16
    close(out.astype(jnp.float32), recurrence(*(
        np.asarray(a.astype(jnp.float32)) for a in low)), tol=3e-2)


def test_the_functional_is_a_primitive_with_gradients_on_the_tape():
    args, w = operands(40, seed=5)
    tensors = [paddle.to_tensor(np.asarray(a), stop_gradient=False)
               for a in args]
    out = F.kda_chunk(*tensors, chunk=16)
    close(out._read(), recurrence(*args))
    (out * paddle.to_tensor(np.asarray(w))).sum().backward()
    _, want = value_and_grads(recurrence_jax, args, w)
    for t, r in zip(tensors, want):
        close(t.grad._read(), r)


def test_it_says_what_it_takes():
    (q, k, v, g, beta), _ = operands(16)
    with pytest.raises(ValueError, match="chunk 48 is not one of"):
        K.kda_chunk(q, k, v, g, beta, chunk=48)
    with pytest.raises(ValueError, match="per channel"):
        K.kda_chunk(q, k, v, g[..., :1], beta)
    with pytest.raises(ValueError, match="must match q"):
        K.kda_chunk(q, k, v, g, beta[..., None])


def test_the_solve_is_the_inverse_of_a_unit_lower_triangle():
    rng = np.random.default_rng(6)
    for size in (16, 32, 64, 128):
        n = np.tril(rng.standard_normal((size, size)), -1).astype("f4") * 0.1
        x = np.asarray(K._solve(jnp.asarray(n)), np.float64)
        close(x @ (np.eye(size) + n), np.eye(size), tol=1e-5)


@pytest.mark.parametrize("chunk", K.CHUNKS)
def test_the_bodys_running_sum_and_its_transpose_are_float32s(chunk):
    """The chunk body makes the decay's running sum itself: against
    numpy's float64 ``cumsum`` (and the reversed one), at an ordinary
    decay and at -20 a step (a chunk of 128 then reaches -2,560)."""
    rng = np.random.default_rng(8)
    for g_min in (-1.0, -20.0):
        g = (g_min * rng.random((chunk, 128))).astype("f4")
        want = np.cumsum(g.astype("f8"), axis=0)
        close(jax.jit(K._running_sum)(g), want, tol=1e-6)
        close(jax.jit(lambda x: K._running_sum(x, reverse=True))(g),
              np.cumsum(g.astype("f8")[::-1], axis=0)[::-1], tol=1e-6)


def test_no_running_sum_is_left_to_xla_round_the_kernels():
    """Forward and backward, the only scans over positions are inside
    the two ``pallas_call``s: XLA makes a ``cumsum`` over a reshaped
    axis a window scan over the whole decay, three times a layer a
    step."""
    args, w = operands(40, seed=9)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(K.kda_chunk(*a, chunk=16, how="interpret") * w),
        argnums=(0, 1, 2, 3, 4)))(*args)
    names = []

    def walk(part):
        for eqn in part.eqns:
            names.append(eqn.primitive.name)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jaxpr.jaxpr)
    assert names.count("pallas_call") == 2
    assert not [n for n in names if n.startswith("cum")
                or n.startswith("reduce_window")], names


def test_the_chunks_gauge_is_set_while_a_kernel_call_is_traced():
    from paddle_tpu.observability import metrics
    args, w = operands(40, seed=7)
    value_and_grads(lambda *a: K.kda_chunk(*a, chunk=16, how="interpret"),
                    args, w)
    got = {labels: m.value for (name, labels), m in
           metrics.registry()._metrics.items() if name == "kda.chunks"}
    shape = "b2h2s48dk16dv16c16"
    want = {k for k in got if shape in str(k)}
    assert len(want) == 2 and all(got[k] == 2 * 2 * 3 for k in want)


# ------------------------------------------- the glue in the flat layout
def four_dimensional_chunk(q, k, v, g, beta, chunk):
    """``kda_chunk``'s glue as it was written on [B, S, H, d], each
    per-head reduction over the last axis: the witness of the flat form
    (``K._fold``), round the same core."""
    f32, cd = jnp.float32, q.dtype

    def l2(x):
        x = x.astype(f32)
        return x * jax.lax.rsqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + K.L2_EPS)

    s = q.shape[1]
    pad = -s % chunk
    bf = beta.astype(f32)[..., None]
    kn = l2(k)

    def padded(x):
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))

    return K._core(
        padded((l2(q) * q.shape[-1] ** -0.5).astype(cd)),
        padded(kn.astype(cd)), padded((kn * bf).astype(cd)),
        padded((v.astype(f32) * bf).astype(cd)), padded(g.astype(f32)),
        chunk, "xla")[:, :s]


def four_dimensional_norm(o, weight, gate, eps):
    """The gated RMS norm as ``models/kimi_linear.py`` wrote it on
    ``o`` [B, S, H, d]."""
    x = o.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    x = x * weight.astype(jnp.float32) * jax.nn.sigmoid(
        gate.astype(jnp.float32).reshape(o.shape))
    return x.astype(o.dtype).reshape(*o.shape[:2], -1)


# float32: two orders of one float32 sum.  bfloat16: both sides cast
# the same float32 values but for their last bit, so an entry here and
# there lands on the other side of a rounding (2^-8 of it)
GLUE_TOL = {"float32": 1e-6, "bfloat16": 2 ** -7}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 3])
def test_the_flat_glue_is_the_four_dimensional_formulas(heads, dtype):
    """L2 norms, scale and the ``beta`` fold as products with the 0/1
    head indicator on [B, S, H * d], with their hand-written backward
    (``dbeta`` and the norms' among it), against the reductions over
    the last of four axes left to autodiff: the result and every
    gradient, on a row (40) that is no multiple of the chunk (16)."""
    args, w = operands(40, dv=32, seed=10, heads=heads)
    low = tuple(a.astype(dtype) for a in args[:3]) + args[3:]
    got = value_and_grads(
        lambda *a: K.kda_chunk(*a, chunk=16, how="xla").astype("f4"), low, w)
    want = value_and_grads(
        lambda *a: four_dimensional_chunk(*a, chunk=16).astype("f4"), low, w)
    close(got[0], want[0], tol=GLUE_TOL[dtype])
    for g, r, like in zip(got[1], want[1], low):
        assert g.shape == like.shape and g.dtype == like.dtype
        close(g.astype("f4"), r.astype("f4"), tol=GLUE_TOL[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [2, 3])
def test_the_flat_gated_norm_is_the_four_dimensional_one(heads, dtype):
    """``gated_head_norm`` on [B, S, H * d] against the norm over the
    last of four axes: the result and the gradients of o, the weight and
    the gate."""
    d, eps = 16, 1e-5
    rng = np.random.default_rng(11)
    o, gate, w = (jnp.asarray(rng.standard_normal((2, 24, heads * d)), dtype)
                  for _ in range(3))
    weight = jnp.asarray(1 + 0.1 * rng.standard_normal(d), "f4")

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype("f4") * w.astype("f4")),
            argnums=(0, 1, 2)))(o, weight, gate)

    got = both(lambda o, wt, g: K.gated_head_norm(o, wt, g, heads, eps))
    want = both(lambda o, wt, g: four_dimensional_norm(
        o.reshape(2, 24, heads, d), wt, g, eps))
    close(got[0], want[0], tol=GLUE_TOL[dtype])
    for g, r, like in zip(got[1], want[1], (o, weight, gate)):
        assert g.shape == like.shape and g.dtype == like.dtype
        close(g.astype("f4"), r.astype("f4"), tol=GLUE_TOL[dtype])


def test_the_head_sums_and_spreads_are_float32_and_exact():
    """A spread is the value itself on each of the head's channels, a
    sum each head's float32 sum: no bfloat16 pass."""
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((2, 8, 3 * 16)) * 1e3, "f4")
    want = np.asarray(x, "f8").reshape(2, 8, 3, 16).sum(-1)
    got = jax.jit(lambda x: K.head_sum(x, 3))(x)
    assert got.dtype == jnp.float32
    close(got, want, tol=1e-6)
    r = jnp.asarray(rng.standard_normal((2, 8, 3)), "f4")
    spread = jax.jit(lambda r: K.head_spread(r, 48))(r)
    assert spread.dtype == jnp.float32
    assert np.array_equal(np.asarray(spread),
                          np.repeat(np.asarray(r), 16, axis=-1))


def test_a_policy_that_keeps_products_keeps_no_spread(capsys):
    """Under the fourth cell's recompute policy (products and kernel
    results are kept, the rest is made again) the core's operands and
    the gated norm's result are made again from the [B, S, H] sums, not
    from their spreads: a spread seen as a product is a float32
    [B, S, H * d] residual, four a layer."""
    from jax.ad_checkpoint import print_saved_residuals

    from paddle_tpu.distributed.fleet.recompute import _POLICIES
    s, h, d = 32, 2, 16
    rng = np.random.default_rng(13)

    def layer(q, k, v, g, beta, gate, weight, proj):
        q, k, v = (jnp.sin(x) for x in (q, k, v))       # made in the block
        o = K.kda_chunk(*(x.reshape(1, s, h, d) for x in (q, k, v, g)), beta,
                        chunk=16, how="xla").reshape(1, s, h * d)
        return K.gated_head_norm(o, weight, gate, h, 1e-5) @ proj

    flat = [jnp.asarray(rng.standard_normal((1, s, h * d)), "f4")
            for _ in range(3)]
    print_saved_residuals(
        jax.checkpoint(layer, policy=_POLICIES["dots_and_kernels_saveable"]),
        *flat, -jnp.ones((1, s, h * d)), jnp.full((1, s, h), 0.5),
        flat[0], jnp.ones(d), jnp.ones((h * d, 8)))
    made = [line for line in capsys.readouterr().out.splitlines()
            if "from the argument" not in line]
    assert len([m for m in made if m.startswith(f"f32[1,{s},{h}]")]) == 3
    assert not [m for m in made if m.startswith(f"f32[1,{s},{h * d}]")], made
