"""Fused multi-tensor optimizer path: parity, views, capture, comms.

The fused path (optimizer/flat.py + ops/pallas/fused_optimizer.py) must
be BIT-EXACT against the per-param path on CPU for every supported
optimizer x dtype x clip x decay combination. Test grads are
integer-valued so the single-reduction global-norm clip sums exactly in
any association order — elementwise update arithmetic is order-free, so
everything downstream stays bitwise comparable.
"""
import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.nn as nn
from paddle_tpu import optimizer as opt
from paddle_tpu.core import state as st
from paddle_tpu.nn import ClipGradByGlobalNorm

SHAPES = [(6, 3), (17,), (2, 2, 2)]


@pytest.fixture(autouse=True)
def _fused_on():
    yield
    st.set_flags({"fused_opt": True})


def _params(dtype="float32", seed=0):
    rng = np.random.default_rng(seed)
    ps = []
    for s in SHAPES:
        v = rng.integers(-4, 5, s).astype("float32")
        p = pt.Parameter(v)
        if dtype != "float32":
            p._write(p._read().astype(dtype))
        ps.append(p)
    return ps


def _grads(step, seed=1):
    rng = np.random.default_rng(seed + step)
    return [rng.integers(-3, 4, s).astype("float32") for s in SHAPES]


def _factories():
    return {
        "sgd": lambda ps, **kw: opt.SGD(0.1, parameters=ps, **kw),
        "momentum": lambda ps, **kw: opt.Momentum(
            0.1, 0.9, parameters=ps, use_nesterov=True, **kw),
        "adam": lambda ps, **kw: opt.Adam(0.05, parameters=ps, **kw),
        "adamw": lambda ps, **kw: opt.AdamW(
            0.05, parameters=ps, weight_decay=0.1, **kw),
    }


def _run(name, fused, dtype, clip, decay, steps=3):
    st.set_flags({"fused_opt": fused})
    ps = _params(dtype)
    kw = {}
    if clip:
        kw["grad_clip"] = ClipGradByGlobalNorm(2.0)
    if dtype != "float32":
        kw["multi_precision"] = True
    if decay and name != "adamw":  # adamw decay is decoupled (built in)
        kw["weight_decay"] = decay
    o = _factories()[name](ps, **kw)
    for i in range(steps):
        for p, g in zip(ps, _grads(i)):
            gv = g if dtype == "float32" else g.astype(dtype)
            p.grad = pt.to_tensor(gv)
        o.step()
        o.clear_grad()
    out = {f"p{i}": np.asarray(p._read()) for i, p in enumerate(ps)}
    for i, p in enumerate(ps):
        p.name = f"w{i}"
    # state_dict gives both layouts alike (a beta pow is ONE 0-d pair a
    # bucket on the fused path and one a parameter on the other, emitted
    # per parameter by both)
    for key, t in o.state_dict().items():
        if key in ("@step", "LR_Scheduler"):
            continue
        out[key] = np.asarray(t._read())
        assert "_pow" not in key or out[key].shape == ()
    return out, o


@pytest.mark.parametrize("name", ["sgd", "momentum", "adam", "adamw"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("clip", [False, True])
def test_fused_bitwise_parity(name, dtype, clip):
    ref, _ = _run(name, fused=False, dtype=dtype, clip=clip, decay=None)
    got, o = _run(name, fused=True, dtype=dtype, clip=clip, decay=None)
    assert o._flat, "fused path did not engage"
    assert set(got) == set(ref)
    for k in ref:
        assert np.array_equal(ref[k], got[k]), f"{k} differs"


@pytest.mark.parametrize("name,dtype,decay", [
    ("sgd", "float32", opt.L2Decay(0.5)),
    ("momentum", "float32", opt.L2Decay(0.5)),
    ("adam", "float32", opt.L2Decay(0.5)),
    ("sgd", "float32", opt.L1Decay(0.3)),
    ("adam", "bfloat16", opt.L2Decay(0.5)),
    ("adamw", "bfloat16", None),  # decoupled decay x master weights
])
def test_fused_parity_with_regularizer(name, dtype, decay):
    ref, _ = _run(name, fused=False, dtype=dtype, clip=True, decay=decay)
    got, o = _run(name, fused=True, dtype=dtype, clip=True, decay=decay)
    assert o._flat
    for k in ref:
        assert np.array_equal(ref[k], got[k]), f"{k} differs"


# ---------------------------------------------------------------- views --
def test_clear_grad_zeroes_flat_bucket_in_one_op():
    """Satellite: set_to_zero=True zeroes the flat grad bucket with ONE
    op; the per-param grad views observe the zeros lazily."""
    ps = _params()
    o = opt.Adam(0.01, parameters=ps)
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    o.step()
    grads_before = [p.grad for p in ps]
    o.clear_grad(set_to_zero=True)
    # identity stable, bound as views, caches invalidated (lazy zeros)
    st0 = o._flat[0].grad_store
    for p, g0 in zip(ps, grads_before):
        assert p.grad is g0
        assert p.grad._flat_view is not None
        # no per-view zero materialized yet: caches still anchor the
        # pre-zero flat array, so the zeros arrive lazily on read
        assert p.grad._flat_src is not st0.storage._data
    assert not np.any(np.asarray(st0.storage._read()))
    for p in ps:
        assert not np.any(np.asarray(p.grad._read()))
    # accumulation into the zeroed views still works
    for p, g in zip(ps, _grads(1)):
        p._accumulate_grad(pt.to_tensor(g)._read())
    np.testing.assert_array_equal(np.asarray(ps[0].grad._read()),
                                  _grads(1)[0])


def test_fused_eager_dispatches_o_buckets():
    """The eager fused update dispatches O(buckets) kernels and never
    walks the per-param _update."""
    from paddle_tpu.ops.pallas import fused_optimizer as fo
    ps = _params()
    o = opt.AdamW(0.01, parameters=ps)
    calls = []
    orig_fused, orig_upd = fo.fused_update, opt.AdamW._update

    def counting(*a, **k):
        calls.append("fused")
        return orig_fused(*a, **k)

    def no_per_param(self, *a, **k):  # pragma: no cover - must not run
        calls.append("per-param")
        return orig_upd(self, *a, **k)
    fo.fused_update = counting
    opt.AdamW._update = no_per_param
    try:
        for i in range(2):
            for p, g in zip(ps, _grads(i)):
                p.grad = pt.to_tensor(g)
            o.step()
            o.clear_grad()
    finally:
        fo.fused_update = orig_fused
        opt.AdamW._update = orig_upd
    assert calls == ["fused", "fused"]  # one kernel per bucket per step
    assert len(o._flat) == 1


def test_state_dict_roundtrip_fused_unfused():
    """fused -> per-param and per-param -> fused state_dict round-trips
    continue training bit-exact vs an uninterrupted run."""
    def seq(fused_a, fused_b, k=2):
        st.set_flags({"fused_opt": fused_a})
        ps = _params()
        o = opt.AdamW(0.05, parameters=ps, weight_decay=0.1)
        for i, p in enumerate(ps):
            p.name = f"w{i}"
        for i in range(k):
            for p, g in zip(ps, _grads(i)):
                p.grad = pt.to_tensor(g)
            o.step()
            o.clear_grad()
        sd = o.state_dict()
        st.set_flags({"fused_opt": fused_b})
        o2 = opt.AdamW(0.05, parameters=ps, weight_decay=0.1)
        o2.set_state_dict(sd)
        for i in range(k, 2 * k):
            for p, g in zip(ps, _grads(i)):
                p.grad = pt.to_tensor(g)
            o2.step()
            o2.clear_grad()
        return [np.asarray(p._read()) for p in ps]

    base = seq(False, False)
    for a, b in [(True, False), (False, True), (True, True)]:
        got = seq(a, b)
        for x, y in zip(base, got):
            assert np.array_equal(x, y), f"roundtrip {a}->{b} differs"


def _named_params():
    ps = _params()
    for i, p in enumerate(ps):
        p.name = f"w{i}"
    return ps


def _adam_steps(o, ps, lo, hi):
    for i in range(lo, hi):
        for p, g in zip(ps, _grads(i)):
            p.grad = pt.to_tensor(g)
        o.step()
        o.clear_grad()


@pytest.mark.parametrize("saved", ["0d", "[1]", "full_shape"])
@pytest.mark.parametrize("path", ["per_param_to_fused", "fused_to_per_param"])
def test_saved_power_of_any_shape_continues_the_bias_correction(path, saved):
    """Both paths write a power 0-d; a checkpoint that holds it at ``[1]``
    (the reference framework's) or at the parameter's full shape (this
    repo's older ones) loads as the same number and the next steps are an
    uninterrupted run's, on either path."""
    st.set_flags({"fused_opt": False})
    ref = _named_params()
    _adam_steps(opt.Adam(0.05, parameters=ref), ref, 0, 4)

    fused_a = path == "fused_to_per_param"
    st.set_flags({"fused_opt": fused_a})
    ps = _named_params()
    o = opt.Adam(0.05, parameters=ps)
    _adam_steps(o, ps, 0, 2)
    assert bool(o._flat) == fused_a
    sd = o.state_dict()
    pows = [k for k in sd if k.endswith("_pow")]
    assert len(pows) == 2 * len(ps)
    for k in pows:
        v = sd[k]._read()
        assert v.shape == () and v.dtype == np.float32
        shape = {"0d": (), "[1]": (1,),
                 "full_shape": SHAPES[int(k[1])]}[saved]
        sd[k] = np.full(shape, np.asarray(v), "float32")

    st.set_flags({"fused_opt": not fused_a})
    o2 = opt.Adam(0.05, parameters=ps)
    o2.set_state_dict(sd)
    for name, beta in (("beta1_pow", 0.9), ("beta2_pow", 0.999)):
        for t in o2._accumulators[name].values():
            v = t._read()
            assert v.shape == () and v.dtype == np.float32
            assert np.float32(v) == np.float32(beta) * np.float32(beta)
    _adam_steps(o2, ps, 2, 4)
    assert bool(o2._flat) == (not fused_a)
    for x, p in zip(ref, ps):
        assert np.array_equal(np.asarray(x._read()), np.asarray(p._read()))
    # no power of more than one element exists on either path
    for store in (o._accumulators, o2._accumulators):
        assert all(t.size == 1 for n in ("beta1_pow", "beta2_pow")
                   for t in store.get(n, {}).values())


def test_saved_power_of_unequal_elements_is_refused():
    ps = _named_params()
    o = opt.Adam(0.05, parameters=ps)
    _adam_steps(o, ps, 0, 1)
    sd = o.state_dict()
    bad = np.full(SHAPES[0], 0.9, "float32")
    bad[0, 0] = 0.81
    sd["w0.beta1_pow"] = bad
    with pytest.raises(ValueError, match=r"w0\.beta1_pow.*one number.*"
                       r"\(6, 3\)"):
        opt.Adam(0.05, parameters=ps).set_state_dict(sd)


def test_resume_from_checkpoint_parity():
    """Save/restore mid-run through state_dict (the checkpoint path)
    matches an uninterrupted fused run."""
    ps = _named_params()
    o = opt.Adam(0.05, parameters=ps)
    _adam_steps(o, ps, 0, 4)
    ref = [np.asarray(p._read()) for p in ps]

    ps2 = _named_params()
    o2 = opt.Adam(0.05, parameters=ps2)
    _adam_steps(o2, ps2, 0, 2)
    sd = o2.state_dict()
    wsd = {f"w{i}": pt.Tensor(p._read()) for i, p in enumerate(ps2)}
    # fresh process analog: new params + optimizer, restore both
    ps3 = _params(seed=7)
    for i, p in enumerate(ps3):
        p.name = f"w{i}"
        p._write(wsd[f"w{i}"]._read())
    o3 = opt.Adam(0.05, parameters=ps3)
    o3.set_state_dict(sd)
    _adam_steps(o3, ps3, 2, 4)
    for x, p in zip(ref, ps3):
        assert np.array_equal(x, np.asarray(p._read()))


# ------------------------------------------------------------- capture --
def test_captured_step_carry_is_flat():
    """A jit-captured train step threads flat buckets, not per-param
    state: the carry is O(buckets), and windows run on it."""
    pt.seed(0)
    net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 32),
                        nn.ReLU(), nn.Linear(32, 32), nn.ReLU(),
                        nn.Linear(32, 32), nn.ReLU(), nn.Linear(32, 4))
    o = opt.AdamW(1e-2, parameters=net.parameters())
    n_params = len(net.parameters())
    assert n_params >= 10

    @pt.jit.to_static
    def step(x, y):
        loss = nn.functional.cross_entropy(net(x), y)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    rng = np.random.default_rng(0)

    def batch():
        return (pt.to_tensor(rng.normal(size=(4, 8)).astype("float32")),
                pt.to_tensor(rng.integers(0, 4, (4,)).astype("int64")))

    warm = batch()
    step(*warm)
    exe = list(step._cache.values())[0]
    carry_idx, _ = exe.state_split()
    # param flat + master-less fp32: params, m1, m2 buckets + grads
    # + 2 beta pows (+ RNG etc.) — far below per-param counts
    assert len(carry_idx) < n_params, \
        f"carry {len(carry_idx)} not flat (params={n_params})"
    assert len(carry_idx) <= 8
    # windows run unchanged on the flat carry
    batches = [batch() for _ in range(3)]
    ref_losses = [float(step(*b)) for b in batches]
    w = pt.jit.WindowRunner(step, warm, length=3)
    stacks = w.stage([batch() for _ in range(3)])
    outs = w.run(*stacks)
    assert len(outs) == 3 and all(np.isfinite(float(x)) for x in outs)
    assert float(outs[-1]) < ref_losses[0] * 2  # sane continuation


def test_captured_fused_matches_eager_fused():
    pt.seed(3)
    net = nn.Linear(6, 3)
    o = opt.Adam(1e-2, parameters=net.parameters())
    rng = np.random.default_rng(3)
    xs = [rng.normal(size=(4, 6)).astype("float32") for _ in range(4)]

    def loss_step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    eager = [float(loss_step(pt.to_tensor(x))) for x in xs[:2]]
    cap = pt.jit.to_static(loss_step)
    compiled = [float(cap(pt.to_tensor(x))) for x in xs[2:]]
    # continue eagerly after compiled steps: state stays coherent
    cont = float(loss_step(pt.to_tensor(xs[0])))
    assert all(np.isfinite(v) for v in eager + compiled + [cont])
    assert cont < eager[0]


_ARITH = {"add", "sub", "mul", "div", "sqrt", "rsqrt", "max", "min", "pow",
          "integer_pow", "neg", "sign", "abs", "square"}


def _bert_base_shapes(hidden, layers, vocab, seq):
    """BERT-base's parameter set, one entry a tensor (its structure is
    what counts: the op counts do not depend on the widths)."""
    h, i4 = hidden, 4 * hidden
    shapes = [(vocab, h), (seq, h), (2, h), (h,), (h,)]  # embeddings + LN
    for _ in range(layers):
        shapes += [(h, h), (h,)] * 4                 # q/k/v/out
        shapes += [(h,), (h,)]                       # attn LN
        shapes += [(h, i4), (i4,), (i4, h), (h,)]    # ffn
        shapes += [(h,), (h,)]                       # ffn LN
    return shapes + [(h, h), (h,), (h,), (h,), (h, 2), (2,)]  # pooler/heads


def _update_arith_ops(shapes, kind, fused):
    """Arithmetic equations, sub-jaxprs included, of the captured
    optimizer-only step over ``shapes``."""
    import jax
    from paddle_tpu.analysis.program import all_eqns
    st.set_flags({"fused_opt": fused})
    rng = np.random.default_rng(0)
    params = [pt.Parameter(rng.normal(size=s).astype("float32") * 0.02)
              for s in shapes]
    o = {"adamw": opt.AdamW, "momentum": opt.Momentum}[kind](
        learning_rate=1e-3, parameters=params)
    for p in params:
        p.grad = pt.to_tensor(
            rng.integers(-2, 3, p.shape).astype("float32"))

    @pt.jit.to_static
    def upd():
        o.step()
        o.clear_grad(set_to_zero=True)
        return params[0]

    upd()
    exe = list(upd._cache.values())[0]
    jaxpr = jax.make_jaxpr(exe._pure)(*[t._read() for t in exe.capt_state])
    return sum(eqn.primitive.name in _ARITH for eqn, _ in all_eqns(jaxpr))


@pytest.mark.parametrize("kind", ["adamw", "momentum"])
def test_hlo_update_op_reduction_10x(kind):
    """Acceptance: traced-step update-op count drops >= 10x at a
    BERT-base-structured param set (size-independent)."""
    shapes = _bert_base_shapes(hidden=16, layers=2, vocab=64, seq=16)
    fused = _update_arith_ops(shapes, kind, fused=True)
    per_param = _update_arith_ops(shapes, kind, fused=False)
    assert per_param / max(fused, 1) >= 10.0, (per_param, fused)


# ---------------------------------------------------------------- amp --
def test_grad_scaler_bucketed_unscale_and_skip():
    import paddle_tpu.amp as amp
    ps = _params()
    o = opt.SGD(0.1, parameters=ps)
    # build the buckets with one clean step
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    o.step()
    o.clear_grad()
    before = [np.asarray(p._read()) for p in ps]
    scaler = amp.GradScaler(init_loss_scaling=1024.0)
    bad = _grads(1)
    bad[1][0] = np.inf
    for p, g in zip(ps, bad):
        p.grad = pt.to_tensor(g)
    scaler.step(o)
    assert scaler._scale == 512.0  # inf seen through the flat bucket
    for x, p in zip(before, ps):
        assert np.array_equal(x, np.asarray(p._read()))  # step skipped


def test_grad_scaler_fused_parity_with_per_param():
    import paddle_tpu.amp as amp

    def run(fused):
        st.set_flags({"fused_opt": fused})
        ps = _params()
        o = opt.SGD(0.1, parameters=ps)
        scaler = amp.GradScaler(init_loss_scaling=8.0)
        for i in range(3):
            for p, g in zip(ps, _grads(i)):
                p.grad = pt.to_tensor(g * 8.0)
            scaler.step(o)
            scaler.update()
            o.clear_grad()
        return [np.asarray(p._read()) for p in ps]

    a, b = run(False), run(True)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


# ------------------------------------------------------------ guard --
def _powers(o):
    """Every beta pow the optimizer holds, per parameter or per bucket."""
    ts = [t for n in ("beta1_pow", "beta2_pow")
          for t in o._accumulators.get(n, {}).values()]
    ts += [t for grp in (o._flat or ()) for t in (grp.b1p, grp.b2p)]
    vals = [np.asarray(t._read()) for t in ts]
    assert vals and all(v.shape == () for v in vals)
    return vals


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "per_param"])
def test_step_guard_skip_is_bitwise_noop(fused):
    from paddle_tpu.resilience import StepGuard
    st.set_flags({"fused_opt": fused})
    nan = pt.to_tensor(np.float32(np.nan))
    # a skipped FIRST step leaves the powers it created at 1
    ps = _params()
    o = opt.Adam(0.05, parameters=ps)
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    StepGuard(max_bad_steps=3).guarded_step(o, nan)
    assert all(v == 1.0 for v in _powers(o))

    ps = _params()
    o = opt.Adam(0.05, parameters=ps)
    guard = StepGuard(max_bad_steps=3)
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    loss = pt.to_tensor(np.float32(1.0))
    guard.guarded_step(o, loss)
    o.clear_grad()
    assert bool(o._flat) == fused
    pow_snap = _powers(o)
    assert all(v < 1.0 for v in pow_snap)
    snap = [np.asarray(p._read()) for p in ps]
    m_snap = np.asarray(o._accumulators["moment1"][id(ps[0])]._read())
    bad = _grads(1)
    bad[0][0] = np.nan
    for p, g in zip(ps, bad):
        p.grad = pt.to_tensor(g)
    guard.guarded_step(o, nan)
    o.clear_grad()
    for x, p in zip(snap, ps):
        assert np.array_equal(x, np.asarray(p._read()))
    assert np.array_equal(
        m_snap, np.asarray(o._accumulators["moment1"][id(ps[0])]._read()))
    assert _powers(o) == pow_snap
    assert guard.bad_streak == 1


# ------------------------------------------------------------- comms --
def test_data_parallel_bucketed_grad_sync():
    import paddle_tpu.distributed as dist
    wrapped = dist.DataParallel(nn.Linear(8, 4))
    x = pt.to_tensor(np.random.default_rng(0).normal(
        size=(16, 8)).astype("float32"))
    loss = (wrapped(x) ** 2).mean()
    loss.backward()
    before = [np.asarray(p.grad._read())
              for p in wrapped.parameters() if p.grad is not None]
    wrapped.apply_collective_grads()
    after = [np.asarray(p.grad._read())
             for p in wrapped.parameters() if p.grad is not None]
    # replicated grads: psum-mean is value-preserving, ONE collective
    # for the single fp32 bucket
    for a, b in zip(before, after):
        np.testing.assert_allclose(a, b, rtol=1e-6)
    assert wrapped._last_sync_collectives == 1


def test_data_parallel_sync_uses_fused_flat_buffer():
    import paddle_tpu.distributed as dist
    net = nn.Linear(8, 4)
    wrapped = dist.DataParallel(net)
    o = opt.SGD(0.1, parameters=wrapped.parameters())
    x = pt.to_tensor(np.random.default_rng(1).normal(
        size=(16, 8)).astype("float32"))
    for _ in range(2):
        loss = (wrapped(x) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad(set_to_zero=True)
    # grads now live in the optimizer's flat bucket; sync must take the
    # zero-repack path (grad views bound + clean)
    loss = (wrapped(x) ** 2).mean()
    loss.backward()
    o._gather_grads(o._flat[0], {id(p): p.grad for p in o._flat[0].params})
    wrapped.apply_collective_grads()
    assert wrapped._last_sync_collectives == 1


# ------------------------------------------------------------ pallas --
def test_pallas_kernel_matches_jnp_twin():
    from paddle_tpu.ops.pallas import fused_optimizer as fo
    import jax.numpy as jnp
    n = 2048
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(n,)).astype("float32"))
    g = jnp.asarray(rng.integers(-3, 4, (n,)).astype("float32"))
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)
    spec = fo.UpdateSpec(kind="adamw", decay=0.1, has_clip=True)
    kw = dict(w=w, g=g, m=m, v=v, b1p=jnp.float32(1.0),
              b2p=jnp.float32(1.0), lr=1e-2, clip_scale=0.5)
    a = fo.fused_update(spec, impl="jnp", **kw)
    b = fo.fused_update(spec, impl="pallas_interpret", **kw)
    for x, y in zip(a, b):
        if x is None:
            assert y is None
            continue
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-6, atol=1e-7)


def test_env_flag_forces_per_param():
    st.set_flags({"fused_opt": False})
    ps = _params()
    o = opt.Adam(0.01, parameters=ps)
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    o.step()
    assert o._flat is None
    assert ps[0]._flat_view is None


def test_exotic_params_fall_back_automatically():
    """Per-param LR / per-param regularizer params stay on the
    per-param path (leftovers) while the rest fuse."""
    ps = _params()
    ps[1].optimize_attr["learning_rate"] = 0.5
    o = opt.Adam(0.05, parameters=ps)
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g)
    o.step()
    assert o._flat and len(o._flat[0].params) == 2
    assert ps[1]._flat_view is None

    # per-param parity for the mixed step
    st.set_flags({"fused_opt": False})
    ps2 = _params()
    ps2[1].optimize_attr["learning_rate"] = 0.5
    o2 = opt.Adam(0.05, parameters=ps2)
    for p, g in zip(ps2, _grads(0)):
        p.grad = pt.to_tensor(g)
    o2.step()
    for a, b in zip(ps, ps2):
        assert np.array_equal(np.asarray(a._read()), np.asarray(b._read()))


def test_mid_run_disable_folds_beta_pows_back():
    """Flipping the flag off after fused Adam steps must defuse (folding
    the per-bucket beta-pow scalars back into per-param accumulators) so
    the per-param path's bias correction continues, not restarts."""
    import warnings

    def run(off_at=None, steps=6):
        st.set_flags({"fused_opt": True})
        ps = _params()
        o = opt.Adam(0.05, parameters=ps)
        for i in range(steps):
            if off_at is not None and i == off_at:
                st.set_flags({"fused_opt": False})
            for p, g in zip(ps, _grads(i)):
                p.grad = pt.to_tensor(g)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                o.step()
            o.clear_grad()
        return [np.asarray(p._read()) for p in ps]

    ref = run()
    mixed = run(off_at=3)
    for a, b in zip(ref, mixed):
        assert np.array_equal(a, b)


def test_capture_step_only_with_clean_prebound_grads():
    """A captured function that ONLY calls step() (grads already bound
    as clean flat views by prior eager fused steps) must compile: the
    gather short-circuit is eager-only, so discovery and replay read the
    same member grads."""
    import warnings

    ps = _params()
    o = opt.AdamW(0.05, parameters=ps)
    for i in range(2):  # eager fused steps bind grad views
        for p, g in zip(ps, _grads(0)):
            p.grad = pt.to_tensor(g)
        o.step()
        if i == 0:
            o.clear_grad()

    @pt.jit.to_static
    def just_step():
        o.step()
        return ps[0]

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        just_step()
        just_step()
    assert not any("eager fallback" in str(x.message) for x in w)


def test_guarded_eager_step_keeps_buckets_clean():
    """StepGuard's blend must write bucket STORAGES, not the per-param
    views — a view write would mark local overrides and force a full
    per-member re-sync (concat) of every bucket on the next step."""
    from paddle_tpu.resilience import StepGuard
    ps = _params()
    o = opt.AdamW(0.05, parameters=ps)
    guard = StepGuard(max_bad_steps=3)
    for i in range(2):
        for p, g in zip(ps, _grads(i)):
            p.grad = pt.to_tensor(g)
        guard.guarded_step(o, pt.to_tensor(np.float32(1.0)))
        o.clear_grad()
    assert o._flat
    for grp in o._flat:
        for store in grp.stores():
            assert not store._dirty
            assert not any(store.local)


def test_bf16_moment_optimizers_without_master_stay_per_param():
    """Flat moment stores are f32; without master weights the per-param
    path keeps accumulators in the param dtype — those buckets must not
    fuse (history-independent), while moment-free SGD still does."""
    st.set_flags({"fused_opt": True})
    ps = _params(dtype="bfloat16")
    o = opt.Momentum(0.1, 0.9, parameters=ps)  # no multi_precision
    for p, g in zip(ps, _grads(0)):
        p.grad = pt.to_tensor(g.astype("bfloat16"))
    o.step()
    assert o._flat is None
    assert ps[0]._flat_view is None

    ps2 = _params(dtype="bfloat16")
    o2 = opt.SGD(0.1, parameters=ps2)  # no moments: fusing stays exact
    for p, g in zip(ps2, _grads(0)):
        p.grad = pt.to_tensor(g.astype("bfloat16"))
    o2.step()
    assert o2._flat


def test_param_view_write_in_capture_declines_to_eager():
    """A captured step that writes a param view (e.g. weight decay /
    EMA-style mutation before step()) cannot compile on the fused path:
    discovery folds the override and resets the dirty flag, so a
    compiled program would silently drop the write. The replay-phase
    GraphBreak must decline capture so every call stays bitwise equal
    to the per-param EAGER reference."""
    import warnings

    def run(fused, capture):
        st.set_flags({"fused_opt": fused})
        ps = _params()
        o = opt.AdamW(0.05, parameters=ps)

        def body():
            ps[0]._write(ps[0]._read() * 0.9)
            for p, g in zip(ps, _grads(0)):
                p.grad = pt.to_tensor(g)
            o.step()
            o.clear_grad()
            return ps[0]

        fn = pt.jit.to_static(body) if capture else body
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            for _ in range(3):
                fn()
        declined = any("eager fallback" in str(x.message) or
                       "pinning" in str(x.message) for x in w)
        return [np.asarray(p._read()) for p in ps], declined

    got, declined = run(fused=True, capture=True)
    ref, _ = run(fused=False, capture=False)
    assert declined, "fused path must decline the capture"
    for a, b in zip(got, ref):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("static", [False, True], ids=["eager", "static"])
@pytest.mark.parametrize("o2", [False, True], ids=["f32", "amp_o2"])
def test_fused_matches_per_param_under_recompute(o2, static):
    """A recomputed block threads its params through jax.checkpoint as
    explicit inputs by substituting their reads; once the fused
    optimizer had bound the params as views of its flat bucket, those
    reads sliced the bucket instead and the block's grads were zero
    from the second step on (found on the chip in PR 24: the one-chip
    GPT-124M losses fell behind the mesh run's, whose sharded params
    stay per-param).  Under AMP O2 one f32 bucket also holds grads of
    two dtypes (bf16 from the recomputed blocks' norms, f32 from the
    final norm).  Fused and per-param must agree: bitwise eagerly."""
    import paddle_tpu.amp as amp
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    def losses(fused):
        st.set_flags({"fused_opt": fused})
        pt.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            max_seq_len=16, dropout=0.0, recompute=True,
            recompute_policy="dots_and_kernels_saveable"))
        model.train()
        opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                     parameters=model.parameters())
        if o2:
            model, opt = amp.decorate(models=model, optimizers=opt,
                                      level="O2", dtype="bfloat16",
                                      master_weight=True)

        def step(ids, labels):
            if o2:
                with amp.auto_cast(level="O2", dtype="bfloat16"):
                    loss = model(ids, labels)
            else:
                loss = model(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        fn = pt.jit.to_static(step) if static else step
        rng = np.random.default_rng(0)
        ids, labels = (pt.to_tensor(
            rng.integers(0, 128, (2, 16)).astype(np.int32))
            for _ in range(2))
        out = [float(fn(ids, labels)) for _ in range(4)]
        assert opt._flat is not None if fused else opt._flat is None
        return out

    fused, per_param = losses(True), losses(False)
    if static:
        # compiled, XLA contracts the two update chains differently: the
        # last ulp of a bf16 weight moves (frozen blocks lose ~1e-1)
        assert fused == pytest.approx(per_param, abs=2e-3)
    else:
        assert fused == per_param
    assert fused[-1] < fused[0]
