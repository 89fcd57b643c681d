"""MoE / expert parallelism tests (reference test pattern:
``test/collective/collective_global_scatter.py`` + moe_layer tests —
routing correctness, capacity semantics, and distributed-vs-dense parity)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.incubate.distributed.models.moe import (MoELayer, MoEMLP,
                                                        moe_dispatch_combine)


def test_dispatch_combine_topk():
    import jax.numpy as jnp
    gates = jnp.asarray([[0.7, 0.2, 0.1],
                         [0.1, 0.8, 0.1],
                         [0.45, 0.1, 0.45]], jnp.float32)
    disp, comb, aux = moe_dispatch_combine(gates, k=2, capacity=2)
    # token 0 -> experts 0 (w .7/.9) and 1; token 1 -> 1, 0; token 2 -> 0/2
    assert disp.shape == (3, 3, 2)
    # every token got its top-1 slot
    assert float(disp[0, 0].sum()) == 1.0
    assert float(disp[1, 1].sum()) == 1.0
    assert float(disp[2, 0].sum()) == 1.0
    # combine weights renormalized over the chosen k
    np.testing.assert_allclose(float(comb[0, 0].sum()), 0.7 / 0.9,
                               rtol=1e-5)
    assert float(aux) > 0.0


def test_capacity_drops_overflow():
    import jax.numpy as jnp
    # all 4 tokens want expert 0; capacity 2 keeps the first two
    gates = jnp.asarray([[0.9, 0.1]] * 4, jnp.float32)
    disp, comb, _ = moe_dispatch_combine(gates, k=1, capacity=2)
    kept = disp[:, 0].sum(axis=-1)
    np.testing.assert_allclose(np.asarray(kept), [1, 1, 0, 0])


def test_moe_mlp_forward_and_grads():
    paddle.seed(0)
    moe = MoEMLP(16, 32, num_experts=4, top_k=2, capacity_factor=2.0)
    x = paddle.to_tensor(
        np.random.default_rng(0).normal(size=(2, 8, 16)).astype("float32"))
    x.stop_gradient = False
    out = moe(x)
    assert tuple(out.shape) == (2, 8, 16)
    assert moe.aux_loss is not None
    (out.sum() + moe.aux_loss).backward()
    for p in (moe.w1, moe.w2, moe.gate.weight):
        assert p.grad is not None
        assert np.isfinite(np.asarray(p.grad._read())).all()
    assert np.isfinite(np.asarray(x.grad._read())).all()


def test_moe_capacity_passthrough_parity():
    """With ample capacity and top_k == num_experts the MoE must compute
    the full convex combination — compare against a dense evaluation of
    every expert."""
    paddle.seed(1)
    moe = MoEMLP(8, 16, num_experts=2, top_k=2, capacity_factor=4.0)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 8)).astype("float32")
    out = np.asarray(moe(paddle.to_tensor(x))._read())

    import jax
    import jax.numpy as jnp
    xf = jnp.asarray(x)
    gates = jax.nn.softmax(xf @ moe.gate.weight._read(), axis=-1)
    dense = 0
    for e in range(2):
        h = jax.nn.gelu(xf @ moe.w1._read()[e] + moe.b1._read()[e])
        y = h @ moe.w2._read()[e] + moe.b2._read()[e]
        dense = dense + gates[:, e:e + 1] * y
    np.testing.assert_allclose(out, np.asarray(dense), atol=1e-5)


def test_gpt_moe_expert_parallel_step():
    """MoE-GPT trains under jit on a (dp, ep) mesh; expert weights keep
    their ep sharding through the compiled update."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM, shard_gpt

    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "ep"])
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=16, dropout=0.0,
                    num_experts=4, moe_top_k=2)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    shard_gpt(model, mesh, dp_axis="dp", mp_axis="none", ep_axis="ep")
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def step(i, l):
        loss = model(i, l)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    pl = [dist.Shard(0), dist.Replicate()]
    losses = []
    for _ in range(3):
        ids = dist.shard_tensor(
            rng.integers(0, 64, (4, 16)).astype(np.int32), mesh, pl)
        labels = dist.shard_tensor(
            rng.integers(0, 64, (4, 16)).astype(np.int32), mesh, pl)
        losses.append(float(step(ids, labels)))
    assert all(np.isfinite(l) for l in losses)
    w1 = model.gpt.blocks[0].mlp.w1._read()
    assert "ep" in str(getattr(w1.sharding, "spec", "")), w1.sharding


def test_moe_layer_api():
    layer = MoELayer(d_model=8, d_hidden=16, num_experts=2, gate="switch")
    out = layer(paddle.to_tensor(np.ones((4, 8), "float32")))
    assert tuple(out.shape) == (4, 8)
    assert layer.moe.top_k == 1
    with pytest.raises(ValueError):
        MoELayer(d_model=8, d_hidden=16, num_experts=2, gate="bogus")


def test_dryrun_multichip_moe():
    """One of the five layouts of ``__graft_entry__.dryrun_multichip(8)``
    (tests/test_models.py holds it to them): dp x ep, the experts
    sharded as in ``test_gpt_moe_expert_parallel_step``."""
    import __graft_entry__ as g
    g._force_virtual_cpu(8)
    g._dryrun_moe(8)
