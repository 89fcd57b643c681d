"""GPT flagship model + recompute + driver hooks.

Mirrors the reference test pattern of training-parity checks
(test/dygraph_to_static model tests; recompute tests in
test/collective/fleet/test_dygraph_recompute*.py — loss/grad parity with
and without recompute)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, shard_gpt


def _cfg(**kw):
    d = dict(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
             max_seq_len=16, dropout=0.0)
    d.update(kw)
    return GPTConfig(**d)


def _batch(cfg, b=2, s=8, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    lab = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    return pt.to_tensor(ids), pt.to_tensor(lab)


def test_gpt_forward_shapes():
    pt.seed(0)
    cfg = _cfg()
    m = GPTForCausalLM(cfg)
    m.eval()
    ids, _ = _batch(cfg)
    logits = m(ids)
    assert logits.shape == [2, 8, cfg.vocab_size]


def test_gpt_trains_jit():
    pt.seed(0)
    cfg = _cfg()
    m = GPTForCausalLM(cfg)
    m.train()
    opt = pt.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())

    @pt.jit.to_static(full_graph=True)
    def step(ids, labels):
        loss = m(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids, lab = _batch(cfg)
    losses = [float(step(ids, lab)) for _ in range(12)]
    assert losses[-1] < losses[0] - 0.5


def test_recompute_grad_parity():
    """Same loss and same grads with recompute on/off (the reference's
    test_dygraph_recompute check)."""

    def run(recompute):
        pt.seed(7)
        cfg = _cfg(recompute=recompute)
        m = GPTForCausalLM(cfg)
        m.train()
        ids, lab = _batch(cfg, seed=3)
        loss = m(ids, lab)
        loss.backward()
        grads = {n: p.grad.numpy() for n, p in m.named_parameters()
                 if p.grad is not None}
        return float(loss), grads

    l0, g0 = run(False)
    l1, g1 = run(True)
    assert abs(l0 - l1) < 1e-5
    assert g0.keys() == g1.keys() and len(g0) > 0
    for k in g0:
        np.testing.assert_allclose(g0[k], g1[k], rtol=1e-4, atol=1e-5)


def test_recompute_policy_grad_parity():
    """Every remat policy (incl. dots_and_kernels_saveable, which keeps
    Pallas flash-attention outputs as residuals) produces the same loss
    and grads — policies trade memory for recompute work, never math."""

    def run(policy):
        pt.seed(7)
        cfg = _cfg(recompute=True, recompute_policy=policy)
        m = GPTForCausalLM(cfg)
        m.train()
        ids, lab = _batch(cfg, seed=3)
        loss = m(ids, lab)
        loss.backward()
        grads = {n: p.grad.numpy() for n, p in m.named_parameters()
                 if p.grad is not None}
        return float(loss), grads

    ref_l, ref_g = run("full")
    for policy in ("dots_saveable", "dots_and_kernels_saveable"):
        l, g = run(policy)
        assert abs(l - ref_l) < 1e-5, policy
        assert g.keys() == ref_g.keys()
        for k in g:
            np.testing.assert_allclose(g[k], ref_g[k], rtol=1e-4,
                                       atol=1e-5, err_msg=policy)


def test_recompute_under_jit():
    pt.seed(0)
    cfg = _cfg(recompute=True)
    m = GPTForCausalLM(cfg)
    m.train()
    opt = pt.optimizer.AdamW(learning_rate=1e-2, parameters=m.parameters())

    @pt.jit.to_static(full_graph=True)
    def step(ids, labels):
        loss = m(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids, lab = _batch(cfg)
    losses = [float(step(ids, lab)) for _ in range(8)]
    assert losses[-1] < losses[0]


def test_recompute_sequential():
    from paddle_tpu import nn
    from paddle_tpu.distributed.fleet.recompute import recompute_sequential
    pt.seed(0)
    seq = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 8))
    x = pt.to_tensor(np.random.default_rng(0).standard_normal(
        (4, 8)).astype(np.float32))
    x.stop_gradient = False
    y = recompute_sequential({"segments": 2}, list(seq), x)
    ref = seq(x)
    np.testing.assert_allclose(y.numpy(), ref.numpy(), rtol=1e-5, atol=1e-6)
    y.backward(pt.to_tensor(np.ones((4, 8), np.float32)))
    assert x.grad is not None


LAYOUTS = ("_dryrun_hybrid", "_dryrun_pipeline", "_dryrun_moe",
           "_dryrun_dp_mp_pp", "_dryrun_zero_mp_pp_1f1b")


def test_shard_gpt_multichip_dryrun(monkeypatch):
    """The driver's ``dryrun_multichip`` contract: the eight virtual CPU
    devices, then exactly the five layouts, in this order (recorders
    stand in their place).  Each layout is a case of its own,
    ``test_dryrun_multichip_<layout>``, beside cases of the same
    subsystem: here, in test_auto_parallel, test_overlap, test_moe and
    test_fleet."""
    import __graft_entry__ as g
    called = []
    for name in LAYOUTS:
        monkeypatch.setattr(
            g, name, lambda n, name=name: called.append((name, n)))
    g.dryrun_multichip(8)
    assert called == [(name, 8) for name in LAYOUTS]
    assert sorted(n for n in vars(g) if n.startswith("_dryrun_")) == \
        sorted(LAYOUTS)


def test_dryrun_multichip_dp_mp_pp():
    """GPipe over pp x Megatron TP over mp x dp on one mesh of eight,
    AdamW steps at ``__graft_entry__``'s toy."""
    import __graft_entry__ as g
    g._force_virtual_cpu(8)
    g._dryrun_dp_mp_pp(8)


def test_entry_compiles():
    import jax

    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 32, 256)
