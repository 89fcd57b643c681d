"""ISSUE-19 training-perf acceptance: selective remat (bitwise policy
family + static-peak drop, and its growth with the batch), fused residual/norm glue
kernels (kernel-vs-twin bitwise parity fwd AND bwd, model-level wiring),
and the double-buffered input pipeline (bitwise loss trajectory +
overlap metrics).

The remat bitwise contract is a FAMILY property: every checkpoint
policy (``full``, ``dots_saveable``, ..., and the new
``everything_saveable`` remat-OFF anchor that saves every residual and
recomputes nothing) runs the same block math through the same
whole-region ``jax.vjp`` — only saved-vs-recomputed residuals differ,
never the arithmetic — so grads are bitwise-identical across the whole
family.  The eager per-op tape sits OUTSIDE the family (its backward
accumulates cotangents in per-op order, ~1e-10 relative off the
region vjp) and is compared at the test_models.py tolerance instead.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.models.gpt import GPTBlock, GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaDecoderLayer

# every non-anchor policy; "full" spells policy=None (recompute all)
_POLICIES = ("full", "dots_saveable", "dots_and_kernels_saveable",
             "transformer_saveable")
_ANCHOR = "everything_saveable"  # save ALL residuals == remat off


def _flag(name):
    return paddle.get_flags(name)[name]


@pytest.fixture()
def metrics_on():
    old = _flag("metrics")
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


# ==========================================================================
# selective remat: bitwise across the policy family
# ==========================================================================

def _gpt_cfg(**kw):
    d = dict(vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
             max_seq_len=16, dropout=0.0)
    d.update(kw)
    return GPTConfig(**d)


def _run_gpt_block(policy):
    paddle.seed(0)
    blk = GPTBlock(_gpt_cfg())
    blk.train()
    blk._recompute = True
    blk._recompute_policy = None if policy == "full" else policy
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 8, 32)).astype("float32"))
    loss = (blk(x) ** 2).mean()
    loss.backward()
    return float(loss), [p.grad.numpy().copy() for p in blk.parameters()
                         if p.grad is not None]


def _run_llama_layer(policy):
    paddle.seed(0)
    layer = LlamaDecoderLayer(LlamaConfig(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=4,
        num_kv_heads=2, max_seq_len=32))
    layer.train()
    layer._recompute = True
    layer._policy = None if policy == "full" else policy
    x = paddle.to_tensor(np.random.default_rng(1).standard_normal(
        (2, 8, 32)).astype("float32"))
    loss = (layer(x) ** 2).mean()
    loss.backward()
    return float(loss), [p.grad.numpy().copy()
                         for p in layer.parameters()
                         if p.grad is not None]


def _run_bf16_master(policy):
    """bf16 O2 forward + fp32 master-weight SGD: the mixed-precision
    step stays inside the bitwise family too (grads AND the post-step
    master weights)."""
    import paddle_tpu.amp as amp
    paddle.seed(0)
    blk = GPTBlock(_gpt_cfg())
    blk.train()
    blk._recompute = True
    blk._recompute_policy = None if policy == "full" else policy
    sgd = paddle.optimizer.SGD(0.1, parameters=blk.parameters(),
                               multi_precision=True)
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 8, 32)).astype("float32"))
    with amp.auto_cast(level="O2", dtype="bfloat16"):
        out = blk(x)
    loss = (out.astype("float32") ** 2).mean()
    loss.backward()
    grads = [p.grad.numpy().copy() for p in blk.parameters()
             if p.grad is not None]
    sgd.step()
    return float(loss), grads + [p.numpy().copy()
                                 for p in blk.parameters()]


@pytest.mark.parametrize("case", ("gpt_block", "llama_layer",
                                  "bf16_master"))
def test_remat_policy_family_bitwise(case):
    """Grads with remat ON (any policy) are BITWISE-identical to the
    everything_saveable anchor (remat off: zero recompute)."""
    run = {"gpt_block": _run_gpt_block, "llama_layer": _run_llama_layer,
           "bf16_master": _run_bf16_master}[case]
    ref_loss, ref_arrs = run(_ANCHOR)
    assert len(ref_arrs) >= 9  # the whole block's parameter set
    for policy in _POLICIES:
        loss, arrs = run(policy)
        assert loss == ref_loss, policy
        assert len(arrs) == len(ref_arrs)
        for i, (a, b) in enumerate(zip(arrs, ref_arrs)):
            assert a.dtype == b.dtype and (a == b).all(), \
                f"{case}/{policy}: array {i} not bitwise"


def test_remat_vs_eager_tape_tolerance():
    """The eager per-op tape (no recompute at all) sits OUTSIDE the
    bitwise family but within the repo's established tolerance
    (test_models.py rtol=1e-4): cotangent accumulation order differs,
    math does not."""
    paddle.seed(0)
    blk = GPTBlock(_gpt_cfg())
    blk.train()
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (2, 8, 32)).astype("float32"))
    loss = (blk(x) ** 2).mean()
    loss.backward()
    eager = [p.grad.numpy().copy() for p in blk.parameters()
             if p.grad is not None]
    ref_loss, ref = _run_gpt_block(_ANCHOR)
    assert float(loss) == pytest.approx(ref_loss, rel=1e-6)
    for a, b in zip(eager, ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_to_static_remat_kwarg_and_validation():
    """``jit.to_static(remat=...)`` runs the converted forward under
    the checkpoint policy (value-identical capture; the recompute only
    moves WHAT the backward keeps live); unknown policy names raise at
    decoration instead of silently training without remat."""
    paddle.seed(0)
    cfg = _gpt_cfg(num_layers=2)
    m = GPTForCausalLM(cfg)
    m.train()
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32))
    lab = paddle.to_tensor(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 8)).astype(np.int32))

    def build(**kw):
        @paddle.jit.to_static(full_graph=True, **kw)
        def fwd(i, l):
            return m(i, l)
        return fwd

    plain = build()
    for remat in (True, "full", "dots_and_kernels_saveable"):
        fused = build(remat=remat)
        for _ in range(2):
            assert float(fused(ids, lab)) == float(plain(ids, lab)), \
                remat

    with pytest.raises(ValueError, match="remat"):
        build(remat="not_a_policy")


def test_model_prepare_remat_flags_blocks():
    """``hapi.Model.prepare(remat=...)`` flips every transformer block
    to the recompute path; ``remat=True`` resolves to the default
    policy; a network with no remat-capable blocks warns."""
    cfg = _gpt_cfg(num_layers=2)
    net = GPTForCausalLM(cfg)
    m = paddle.Model(net)
    m.prepare(paddle.optimizer.SGD(0.1, parameters=net.parameters()),
              remat=True)
    blocks = [b for b in net.gpt.blocks]
    assert all(b._recompute for b in blocks)
    assert all(b._recompute_policy == "dots_and_kernels_saveable"
               for b in blocks)

    plain = nn.Sequential(nn.Linear(4, 4))
    m2 = paddle.Model(plain)
    with pytest.warns(RuntimeWarning, match="remat"):
        m2.prepare(paddle.optimizer.SGD(
            0.1, parameters=plain.parameters()), remat=True)


def _captured_step_peak(batch, **cfg_kw):
    """``static_peak_bytes`` of one captured GPT train step (forward,
    backward, SGD) at ``batch`` rows of ``max_seq_len`` tokens: the
    number the ``hbm.static_peak_bytes{fn}`` gauge exports."""
    paddle.seed(0)
    cfg = _gpt_cfg(vocab_size=128, hidden_size=64, num_heads=4,
                   use_flash_attention=False, **cfg_kw)
    m = GPTForCausalLM(cfg)
    m.train()
    opt = paddle.optimizer.SGD(0.01, parameters=m.parameters())

    @paddle.jit.to_static(full_graph=True)
    def step(i, l):
        loss = m(i, l)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    ids, lab = (paddle.to_tensor(rng.integers(
        0, cfg.vocab_size, (batch, cfg.max_seq_len)).astype(np.int32))
        for _ in range(2))
    step(ids, lab)
    exe = next(iter(step._cache.values()))
    return int(exe.static_peak_bytes)


def test_remat_static_peak_drop():
    """The acceptance gauge: on a multi-layer GPT block stack the
    captured train step's ``static_peak_bytes`` drops >= 25% with remat
    on (measured 52% on this geometry, 47% at four times its width, 56%
    at the full gpt124m hidden=768/seq=256/batch=8 shape).  Single-layer
    stacks can go the OTHER way (nothing upstream to free); the saving
    is a multi-layer property, which is why this config has 4 layers."""
    def peak(remat):
        return _captured_step_peak(
            4, num_layers=4, max_seq_len=128, recompute=remat,
            recompute_policy="dots_and_kernels_saveable")

    p_off, p_on = peak(False), peak(True)
    assert p_on < 0.75 * p_off, (p_off, p_on)


def test_static_peak_bytes_monotone_in_batch():
    """The ``static_peak_bytes`` a captured train step's executable
    carries (what a walk over batch sizes against an HBM budget reads)
    is positive and grows with the batch."""
    p1, p2 = (_captured_step_peak(bs, num_layers=2, max_seq_len=32)
              for bs in (1, 2))
    assert 0 < p1 < p2, (p1, p2)


# ==========================================================================
# fused residual/norm glue kernels: twin parity (PR4/PR11/PR18 gate)
# ==========================================================================

_GEOMS = ((256, 128), (100, 96), (40, 64))  # rect, padded, sub-block


def _glue_inputs(n, h, seed, n_arrays):
    rng = np.random.default_rng(seed)
    return [np.asarray(rng.standard_normal((n, h)), np.float32)
            for _ in range(n_arrays)]


@pytest.mark.parametrize("n,h", _GEOMS)
def test_fused_residual_layer_norm_twin_bitwise(n, h):
    from paddle_tpu.ops.pallas import fused_residual_norm as frn
    x, y, dr, g = _glue_inputs(n, h, 0, 4)
    w = np.asarray(np.random.default_rng(1).standard_normal(h),
                   np.float32)
    b = np.asarray(np.random.default_rng(2).standard_normal(h),
                   np.float32)
    rows = 64  # force a multi-block grid on the 256-row geometry
    k = frn.fused_residual_layer_norm_fwd(x, y, w, b, rows=rows,
                                          interpret=True)
    t = frn.fused_residual_layer_norm_fwd_twin(x, y, w, b, rows=rows)
    for kv, tv in zip(k, t):
        assert (np.asarray(kv) == np.asarray(tv)).all()
    res, _, mean, rstd = (np.asarray(v) for v in k)
    kb = frn.fused_residual_layer_norm_bwd(res, w, mean, rstd, dr, g,
                                           rows=rows, interpret=True)
    tb = frn.fused_residual_layer_norm_bwd_twin(res, w, mean, rstd,
                                                dr, g, rows=rows)
    for kv, tv in zip(kb, tb):
        assert (np.asarray(kv) == np.asarray(tv)).all()


@pytest.mark.parametrize("n,h", _GEOMS)
def test_fused_residual_rms_norm_twin_bitwise(n, h):
    from paddle_tpu.ops.pallas import fused_residual_norm as frn
    x, y, dr, g = _glue_inputs(n, h, 3, 4)
    w = np.asarray(np.random.default_rng(4).standard_normal(h),
                   np.float32)
    rows = 64
    k = frn.fused_residual_rms_norm_fwd(x, y, w, rows=rows,
                                        interpret=True)
    t = frn.fused_residual_rms_norm_fwd_twin(x, y, w, rows=rows)
    for kv, tv in zip(k, t):
        assert (np.asarray(kv) == np.asarray(tv)).all()
    res, _, rstd = (np.asarray(v) for v in k)
    kb = frn.fused_residual_rms_norm_bwd(res, w, rstd, dr, g,
                                         rows=rows, interpret=True)
    tb = frn.fused_residual_rms_norm_bwd_twin(res, w, rstd, dr, g,
                                              rows=rows)
    for kv, tv in zip(kb, tb):
        assert (np.asarray(kv) == np.asarray(tv)).all()


def test_fused_glue_grads_match_reference():
    """The custom_vjp backward against jax.grad of an unfused reference
    chain: same residual/norm math, fp32-stat tolerance."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import fused_residual_norm as frn

    x, y = (jnp.asarray(a) for a in _glue_inputs(48, 64, 7, 2))
    w = jnp.asarray(np.random.default_rng(8).standard_normal(64),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(9).standard_normal(64),
                    jnp.float32)

    def fused(xv, yv, wv, bv):
        r, o = frn.fused_residual_layer_norm(xv, yv, wv, bv,
                                             interpret=True)
        return jnp.sum(r * o)

    def ref(xv, yv, wv, bv):
        r = xv + yv
        r32 = r.astype(jnp.float32)
        mean = jnp.mean(r32, axis=1, keepdims=True)
        var = jnp.mean(jnp.square(r32 - mean), axis=1, keepdims=True)
        o = (r32 - mean) * jax.lax.rsqrt(var + 1e-5) * wv + bv
        return jnp.sum(r * o)

    gf = jax.grad(fused, argnums=(0, 1, 2, 3))(x, y, w, b)
    gr = jax.grad(ref, argnums=(0, 1, 2, 3))(x, y, w, b)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("family", ("gpt", "llama", "bert"))
def test_glue_fusion_model_parity_and_training(family):
    """Flag-gated model wiring: the glue-fused TRAIN forward matches
    the unfused one to fp32-stat tolerance for all three block styles
    (pre-norm GPT/LLaMA via the pending-branch thread, post-LN BERT in
    place), and grads stay finite under remat+glue composition."""
    def build():
        paddle.seed(0)
        if family == "gpt":
            from paddle_tpu.models.gpt import GPTModel
            m = GPTModel(_gpt_cfg(num_layers=2))
        elif family == "llama":
            from paddle_tpu.models.llama import LlamaModel
            m = LlamaModel(LlamaConfig(
                vocab_size=128, hidden_size=32, num_layers=2,
                num_heads=4, num_kv_heads=2, max_seq_len=32))
        else:
            from paddle_tpu.models.bert import BertConfig, BertModel
            m = BertModel(BertConfig(
                vocab_size=64, hidden_size=32, num_layers=2,
                num_heads=4, max_seq_len=16, dropout=0.0))
        m.train()
        return m

    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 64, (2, 8)).astype(np.int32))
    old = _flag("train_glue_fusion")
    try:
        def first(out):
            return out[0] if isinstance(out, tuple) else out

        paddle.set_flags({"train_glue_fusion": False})
        ref = first(build()(ids))
        paddle.set_flags({"train_glue_fusion": True})
        fused_model = build()
        out = first(fused_model(ids))
        np.testing.assert_allclose(out.numpy(), ref.numpy(),
                                   rtol=1e-4, atol=1e-5)
        # grads flow (and stay finite) through the fused chain
        loss = (out ** 2).mean()
        loss.backward()
        grads = [p.grad.numpy() for p in fused_model.parameters()
                 if p.grad is not None]
        assert len(grads) >= 10
        assert all(np.isfinite(g).all() for g in grads)
    finally:
        paddle.set_flags({"train_glue_fusion": old})


def test_glue_fusion_drops_dispatches():
    """Counted through ``core.dispatch._profile_hook``, one eager TRAIN
    forward at one and at two layers (the difference leaves the
    embedding and the final norm out): the fused forward dispatches
    fewer ops a layer, and at least 2 fewer of the glue ops (add and
    norm: 4 -> 2).  Goes with ``train_glue_fusion`` (ROADMAP D2)."""
    from paddle_tpu.core import dispatch
    from paddle_tpu.models.gpt import GPTModel
    glue_ops = ("add", "layer_norm", "rms_norm", "fused_residual_norm")
    old = _flag("train_glue_fusion")

    def per_layer(fused):
        counts = []
        for layers in (1, 2):
            paddle.seed(0)
            m = GPTModel(_gpt_cfg(num_layers=layers,
                                  use_flash_attention=False))
            m.train()
            seen = []
            dispatch._profile_hook = lambda name, t0, t1: seen.append(name)
            try:
                paddle.set_flags({"train_glue_fusion": fused})
                with paddle.no_grad():
                    m(paddle.to_tensor(np.zeros((2, 16), np.int32)))
            finally:
                dispatch._profile_hook = None
                paddle.set_flags({"train_glue_fusion": old})
            counts.append((len(seen),
                           sum(n in glue_ops for n in seen)))
        return (counts[1][0] - counts[0][0], counts[1][1] - counts[0][1])

    (ops, glue), (ops_fused, glue_fused) = per_layer(False), per_layer(True)
    assert ops_fused < ops, (ops, ops_fused)
    assert glue - glue_fused >= 2, (glue, glue_fused)


# ==========================================================================
# async double-buffered input pipeline
# ==========================================================================

class _RegDataset(paddle.io.Dataset):
    """Deterministic regression data (fixed seed, no shuffle in fit)."""

    def __init__(self, n=48, dim=8, seed=0):
        rng = np.random.default_rng(seed)
        self.x = rng.standard_normal((n, dim)).astype("float32")
        self.y = (self.x @ rng.standard_normal(
            (dim, 1)).astype("float32"))

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def _fit_losses(prefetch, window=1, epochs=2):
    old = _flag("train_prefetch")
    paddle.set_flags({"train_prefetch": prefetch})
    try:
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 1))
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.SGD(
            0.05, parameters=net.parameters()), nn.loss.MSELoss())
        losses = []

        class Rec(paddle.callbacks.Callback):
            def on_train_batch_end(self, step, logs=None):
                losses.append(logs["loss"])

        m.fit(_RegDataset(), epochs=epochs, batch_size=8,
              shuffle=False, verbose=0, window=window,
              callbacks=[Rec()])
        return losses
    finally:
        paddle.set_flags({"train_prefetch": old})


@pytest.mark.parametrize("window", (1, 3))
def test_prefetch_loss_trajectory_bitwise(window):
    """Double-buffered staging is value-identical: the full loss
    trajectory matches the synchronous path BITWISE, per-batch and
    windowed both."""
    on = _fit_losses(True, window=window)
    off = _fit_losses(False, window=window)
    assert len(on) == len(off) >= 10
    assert on == off


def test_prefetch_overlap_metrics(metrics_on):
    """CPU smoke for the overlap gauges: with prefetch on, some staging
    ran under the step (input_overlap_frac > 0) and the residual wait
    histogram recorded every serve."""
    import paddle_tpu.observability as obs
    losses = _fit_losses(True)
    assert losses  # trained
    snap = obs.registry().snapshot()["train"]
    assert snap["input_overlap_frac"] > 0.0
    assert snap["input_wait_ms"]["count"] >= len(losses)


def test_prefetch_exhausts_loader_exactly():
    """The feed serves every batch exactly once (no double-consume
    from the staged-ahead batch at epoch end)."""
    n_batches = len(_fit_losses(True, epochs=1))
    assert n_batches == 6  # 48 samples / batch_size 8
