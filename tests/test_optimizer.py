"""Optimizer + LR scheduler + AMP tests."""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import optimizer as opt


def _quadratic_steps(optimizer_factory, n=50):
    """Minimize ||w - 3||^2 and return final w."""
    w = pt.Parameter(np.zeros(4, dtype="float32"))
    o = optimizer_factory([w])
    for _ in range(n):
        loss = ((w - 3.0) ** 2).sum()
        loss.backward()
        o.step()
        o.clear_grad()
    return w.numpy()


def test_sgd_converges():
    w = _quadratic_steps(lambda ps: opt.SGD(0.1, parameters=ps), 100)
    np.testing.assert_allclose(w, np.full(4, 3.0), atol=1e-3)


def test_momentum_converges():
    w = _quadratic_steps(lambda ps: opt.Momentum(0.05, 0.9, parameters=ps),
                         100)
    np.testing.assert_allclose(w, np.full(4, 3.0), atol=5e-2)


def test_adam_converges():
    w = _quadratic_steps(lambda ps: opt.Adam(0.3, parameters=ps), 100)
    np.testing.assert_allclose(w, np.full(4, 3.0), atol=1e-2)


def test_adamw_decay_shrinks_weights():
    w = pt.Parameter(np.full(4, 5.0, dtype="float32"))
    o = opt.AdamW(learning_rate=0.0, weight_decay=0.1, parameters=[w])
    w.grad = pt.zeros([4])
    o.step()
    # lr=0 -> only decay path, which multiplies by (1 - lr*coeff) = 1
    np.testing.assert_allclose(w.numpy(), np.full(4, 5.0))
    o2 = opt.AdamW(learning_rate=0.1, weight_decay=0.5, parameters=[w])
    w.grad = pt.zeros([4])
    o2.step()
    assert (w.numpy() < 5.0).all()


def test_adam_matches_reference_formula():
    w0 = np.array([1.0, -2.0], dtype="float32")
    g = np.array([0.5, 0.3], dtype="float32")
    w = pt.Parameter(w0.copy())
    o = opt.Adam(learning_rate=0.01, parameters=[w])
    w.grad = pt.to_tensor(g.copy())
    o.step()
    m = 0.1 * g
    v = 0.001 * g * g
    m_hat = m / (1 - 0.9)
    v_hat = v / (1 - 0.999)
    ref = w0 - 0.01 * m_hat / (np.sqrt(v_hat) + 1e-8)
    np.testing.assert_allclose(w.numpy(), ref, rtol=1e-5)


_POW_FAMILY = {
    "adam": lambda ps, **kw: opt.Adam(0.01, parameters=ps, **kw),
    "adamw": lambda ps, **kw: opt.AdamW(0.01, parameters=ps,
                                        weight_decay=0.1, **kw),
    "adamax": lambda ps, **kw: opt.Adamax(0.01, parameters=ps, **kw),
    "lamb": lambda ps, **kw: opt.Lamb(0.01, parameters=ps, **kw),
}
_POW_SHAPES = [(5, 3), (7,)]


def _pow_family_run(name, bf16, steps=3):
    """(optimizer, params, float32 grads step by step) after ``steps``
    eager per-parameter steps; the fused path off, as every cell of the
    benchmark runs."""
    from paddle_tpu.core import state as st
    rng = np.random.default_rng(0)
    ps = [pt.Parameter(rng.standard_normal(s).astype("float32"))
          for s in _POW_SHAPES]
    if bf16:
        for p in ps:
            p._write(p._read().astype("bfloat16"))
    w0 = [np.asarray(p._read()).astype("float32") for p in ps]
    grads = [[np.asarray(pt.to_tensor(
        rng.standard_normal(s).astype("float32"))._read().astype(
            "bfloat16" if bf16 else "float32")) for s in _POW_SHAPES]
        for _ in range(steps)]
    st.set_flags({"fused_opt": False})
    try:
        o = _POW_FAMILY[name](ps, multi_precision=bf16)
        for gs in grads:
            for p, g in zip(ps, gs):
                p.grad = pt.to_tensor(g)
            o.step()
            o.clear_grad()
    finally:
        st.set_flags({"fused_opt": True})
    return o, ps, w0, [[g.astype("float32") for g in gs] for gs in grads]


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16_master"])
@pytest.mark.parametrize("name", sorted(_POW_FAMILY))
def test_bias_correction_powers_are_one_number_a_parameter(name, bf16):
    """``beta ** steps`` is the same in every element, so it is kept as
    ONE 0-d float32 a parameter, never at the parameter's shape."""
    steps = 3
    o, ps, _w0, _g = _pow_family_run(name, bf16, steps)
    f32 = np.float32
    pows = {n: s for n, s in o._accumulators.items() if n.endswith("_pow")}
    assert sorted(pows) == (["beta1_pow"] if name == "adamax"
                            else ["beta1_pow", "beta2_pow"])
    for n, store in pows.items():
        beta, want = f32(o._beta1 if n == "beta1_pow" else o._beta2), f32(1)
        for _ in range(steps):
            want = want * beta
        assert len(store) == len(ps)
        for t in store.values():
            v = t._read()
            assert v.shape == () and v.dtype == np.float32
            assert f32(v) == want
    nb = o.state_bytes()
    n_el = sum(int(np.prod(s)) for s in _POW_SHAPES)
    assert nb == {"param": (2 if bf16 else 4) * n_el,
                  "master": 4 * n_el if bf16 else 0,
                  "moments": 8 * n_el,
                  "scalars": 4 * len(ps) * len(pows)}
    from paddle_tpu.observability import metrics
    assert metrics.snapshot()["optimizer"]["state_bytes"] == {
        f"part={k}": v for k, v in nb.items()}


@pytest.mark.parametrize("bf16", [False, True], ids=["f32", "bf16_master"])
@pytest.mark.parametrize("name", ["adam", "adamw"])
def test_adamw_equals_numpy_written_out(name, bf16):
    """Float32 exactness against the update written out in numpy: the
    0-d power broadcast gives every element the float32 that the
    full-shape copy gave it."""
    o, ps, w0, grads = _pow_family_run(name, bf16)
    f32 = np.float32
    lr, b1, b2, eps = f32(0.01), f32(0.9), f32(0.999), f32(1e-8)
    shrink = f32(1.0 - 0.01 * 0.1) if name == "adamw" else f32(1)
    for i, p in enumerate(ps):
        w = w0[i]
        m, v = np.zeros_like(w), np.zeros_like(w)
        b1p = b2p = f32(1)
        for gs in grads:
            g = gs[i]
            w = w * shrink
            m = b1 * m + f32(1 - 0.9) * g
            v = b2 * v + f32(1 - 0.999) * np.square(g)
            b1p, b2p = b1p * b1, b2p * b2
            m_hat, v_hat = m / (f32(1) - b1p), v / (f32(1) - b2p)
            w = w - lr * m_hat / (np.sqrt(v_hat) + eps)
        assert w.dtype == np.float32
        got = o._master_weights[id(p)] if bf16 else p
        np.testing.assert_array_equal(np.asarray(got._read()), w)
        if bf16:
            np.testing.assert_array_equal(
                np.asarray(p._read()),
                np.asarray(pt.to_tensor(w)._read().astype("bfloat16")))


def test_captured_step_state_is_14_bytes_a_bf16_parameter():
    """A ``to_static`` AdamW step under float32 masters holds each weight
    once in bfloat16 and three times in float32 (master, two moments);
    everything else it captures is 0-d (the powers, the LR)."""
    from paddle_tpu.core import state as st
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    params = net.parameters()
    for p in params:
        p._write(p._read().astype("bfloat16"))
    n_el = sum(p.size for p in params)
    st.set_flags({"fused_opt": False})
    try:
        o = opt.AdamW(1e-2, parameters=params, multi_precision=True)

        @pt.jit.to_static
        def step(x, y):
            loss = F.cross_entropy(net(x).astype("float32"), y)
            loss.backward()
            o.step()
            o.clear_grad()
            return loss

        x = pt.to_tensor(np.ones((4, 8), "float32")).astype("bfloat16")
        y = pt.to_tensor(np.arange(4).astype("int64"))
        for _ in range(3):  # eager discovery, compile, compiled
            loss = step(x, y)
    finally:
        st.set_flags({"fused_opt": True})
    assert np.isfinite(float(loss))
    exe, = step._cache.values()
    shaped = [t._data for t in exe.capt_state if t._data.ndim]
    numbers = [t._data for t in exe.capt_state if not t._data.ndim]
    assert sum(a.nbytes for a in shaped) == 14 * n_el
    assert sum(a.size for a in shaped
               if a.dtype == np.float32) == 3 * n_el
    # two powers a parameter and the LR
    assert len(numbers) == 2 * len(params) + 1
    assert o.state_bytes()["scalars"] == 8 * len(params)


def test_multi_precision_master_weights():
    w = pt.Parameter(np.full(4, 1.0, dtype="float32"))
    w._write(w._read().astype("bfloat16"))
    o = opt.SGD(0.001, parameters=[w], multi_precision=True)
    for _ in range(10):
        w.grad = pt.to_tensor(np.full(4, 0.01, dtype="float32"))
        o.step()
    # 10 tiny steps accumulate exactly in the fp32 master copy
    master = o._master_weights[id(w)]
    np.testing.assert_allclose(np.asarray(master), np.full(4, 0.9999),
                               rtol=1e-5)


def test_optimizer_state_dict_roundtrip():
    w = pt.Parameter(np.ones(3, dtype="float32"), name="w")
    o = opt.Adam(0.1, parameters=[w])
    w.grad = pt.ones([3])
    o.step()
    sd = o.state_dict()
    o2 = opt.Adam(0.1, parameters=[w])
    o2.set_state_dict(sd)
    assert o2._step_count == 1
    np.testing.assert_allclose(
        np.asarray(o2._accumulators["moment1"][id(w)]),
        np.asarray(o._accumulators["moment1"][id(w)]))


def test_lr_schedulers():
    from paddle_tpu.optimizer.lr import (
        CosineAnnealingDecay, LinearWarmup, MultiStepDecay, NoamDecay,
        PiecewiseDecay, PolynomialDecay, StepDecay)
    s = StepDecay(0.1, step_size=2, gamma=0.5)
    lrs = []
    for _ in range(5):
        lrs.append(s())
        s.step()
    np.testing.assert_allclose(lrs, [0.1, 0.1, 0.05, 0.05, 0.025])
    w = LinearWarmup(0.1, warmup_steps=4, start_lr=0.0, end_lr=0.1)
    vals = []
    for _ in range(5):
        vals.append(w())
        w.step()
    np.testing.assert_allclose(vals[:4], [0.0, 0.025, 0.05, 0.075])
    c = CosineAnnealingDecay(1.0, T_max=10)
    assert abs(c() - 1.0) < 1e-6
    p = PiecewiseDecay([3, 6], [0.1, 0.01, 0.001])
    assert p() == 0.1


def test_scheduler_drives_optimizer():
    from paddle_tpu.optimizer.lr import StepDecay
    sched = StepDecay(0.1, step_size=1, gamma=0.1)
    w = pt.Parameter(np.zeros(1, dtype="float32"))
    o = opt.SGD(sched, parameters=[w])
    w.grad = pt.ones([1])
    o.step()
    np.testing.assert_allclose(w.numpy(), [-0.1], rtol=1e-6)
    sched.step()
    w.grad = pt.ones([1])
    o.step()
    np.testing.assert_allclose(w.numpy(), [-0.11], rtol=1e-5)


def test_grad_clip_in_optimizer():
    from paddle_tpu.nn import ClipGradByGlobalNorm
    w = pt.Parameter(np.zeros(4, dtype="float32"))
    o = opt.SGD(1.0, parameters=[w], grad_clip=ClipGradByGlobalNorm(1.0))
    w.grad = pt.to_tensor(np.full(4, 100.0, dtype="float32"))
    o.step()
    np.testing.assert_allclose(np.linalg.norm(w.numpy()), 1.0, rtol=1e-4)


def test_amp_auto_cast_o1():
    import paddle_tpu.amp as amp
    x = pt.randn([4, 4])
    y = pt.randn([4, 4])
    with amp.auto_cast(level="O1", dtype="bfloat16"):
        z = pt.matmul(x, y)
        assert str(z.dtype) == "bfloat16"
        s = F.softmax(z)  # black list -> fp32
        assert str(s.dtype) == "float32"
    z2 = pt.matmul(x, y)
    assert str(z2.dtype) == "float32"


def test_amp_grad_scaler_fp16_flow():
    import paddle_tpu.amp as amp
    w = pt.Parameter(np.ones(2, dtype="float32"))
    o = opt.SGD(0.1, parameters=[w])
    scaler = amp.GradScaler(init_loss_scaling=1024.0)
    loss = (w * 2.0).sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    # grad should be 2*1024 before unscale
    np.testing.assert_allclose(w.grad.numpy(), [2048.0, 2048.0])
    scaler.step(o)
    np.testing.assert_allclose(w.numpy(), [0.8, 0.8], rtol=1e-6)


def test_grad_scaler_skips_on_inf():
    import paddle_tpu.amp as amp
    w = pt.Parameter(np.ones(2, dtype="float32"))
    o = opt.SGD(0.1, parameters=[w])
    scaler = amp.GradScaler(init_loss_scaling=1024.0)
    w.grad = pt.to_tensor(np.array([np.inf, 1.0], dtype="float32"))
    scaler.step(o)
    np.testing.assert_allclose(w.numpy(), [1.0, 1.0])  # step skipped
    assert scaler._scale == 512.0  # scale halved


def test_amp_decorate_o2():
    import paddle_tpu.amp as amp
    model = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.BatchNorm1D(8))
    o = opt.Adam(0.1, parameters=model.parameters())
    model, o = amp.decorate(model, o, level="O2", dtype="bfloat16")
    assert str(model[0].weight.dtype) == "bfloat16"
    # norm layers stay fp32
    assert str(model[2].weight.dtype) == "float32"
    assert o._multi_precision
