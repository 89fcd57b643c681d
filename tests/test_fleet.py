"""Fleet hybrid-parallel tests: TP layers parity vs plain layers, sharding
(ZeRO) stages, fleet facade (reference pattern
test/collective/fleet/hybrid_parallel_mp_model.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet


@pytest.fixture(scope="module", autouse=True)
def _env():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                               "pp_degree": 1, "sharding_degree": 1,
                               "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)


def test_topology():
    hcg = fleet.get_hybrid_communicate_group()
    assert hcg.get_data_parallel_world_size() == 4
    assert hcg.get_model_parallel_world_size() == 2
    assert hcg.nranks == 8
    topo = hcg.topology()
    assert topo.world_size() == 8
    assert topo.get_dim("model") == 2
    comm_list = topo.get_comm_list("model")
    assert len(comm_list) == 4 and all(len(g) == 2 for g in comm_list)


def test_column_row_parallel_linear_parity():
    paddle.seed(21)
    col = fleet.ColumnParallelLinear(8, 16, gather_output=False)
    row = fleet.RowParallelLinear(16, 4, input_is_parallel=True)
    paddle.seed(21)
    fc1 = paddle.nn.Linear(8, 16)
    fc2 = paddle.nn.Linear(16, 4)

    np.testing.assert_allclose(col.weight.numpy(), fc1.weight.numpy(),
                               rtol=1e-6)

    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    y_tp = row(col(x))
    y_ref = fc2(fc1(x))
    np.testing.assert_allclose(y_tp.numpy(), y_ref.numpy(), rtol=1e-4,
                               atol=1e-5)

    # weights actually sharded over mp (2-way on the right dims)
    w = col.weight._read()
    assert {s.data.shape for s in w.addressable_shards} == {(8, 8)}
    w = row.weight._read()
    assert {s.data.shape for s in w.addressable_shards} == {(8, 4)}


def test_tp_backward_parity():
    paddle.seed(33)
    col = fleet.ColumnParallelLinear(8, 16, gather_output=False)
    row = fleet.RowParallelLinear(16, 4, input_is_parallel=True)
    paddle.seed(33)
    fc1 = paddle.nn.Linear(8, 16)
    fc2 = paddle.nn.Linear(16, 4)

    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    loss_tp = (row(col(x)) ** 2).mean()
    loss_tp.backward()
    loss_ref = (fc2(fc1(x)) ** 2).mean()
    loss_ref.backward()
    np.testing.assert_allclose(col.weight.grad.numpy(),
                               fc1.weight.grad.numpy(), rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(row.weight.grad.numpy(),
                               fc2.weight.grad.numpy(), rtol=1e-4,
                               atol=1e-6)


def test_vocab_parallel_embedding_parity():
    paddle.seed(5)
    vp = fleet.VocabParallelEmbedding(16, 8)
    paddle.seed(5)
    emb = paddle.nn.Embedding(16, 8)
    np.testing.assert_allclose(vp.weight.numpy(), emb.weight.numpy(),
                               rtol=1e-6)
    ids = paddle.to_tensor(np.array([[0, 3, 15], [7, 8, 2]], dtype=np.int32))
    np.testing.assert_allclose(vp(ids).numpy(), emb(ids).numpy(), rtol=1e-6)
    w = vp.weight._read()
    assert {s.data.shape for s in w.addressable_shards} == {(8, 8)}


def test_parallel_cross_entropy():
    logits = paddle.to_tensor(
        np.random.randn(4, 16).astype(np.float32), stop_gradient=False)
    labels = paddle.to_tensor(np.array([1, 5, 10, 15], dtype=np.int64))
    pce = fleet.ParallelCrossEntropy()
    loss = pce(logits, labels)
    ref = paddle.nn.functional.cross_entropy(logits, labels,
                                             reduction="none")
    np.testing.assert_allclose(loss.numpy().ravel(), ref.numpy().ravel(),
                               rtol=1e-5)


class _TPMLP(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.embed = fleet.VocabParallelEmbedding(32, 16)
        self.fc1 = fleet.ColumnParallelLinear(16, 32, gather_output=False)
        self.fc2 = fleet.RowParallelLinear(32, 16, input_is_parallel=True)
        self.head = paddle.nn.Linear(16, 32)

    def forward(self, ids):
        h = self.embed(ids)
        h = paddle.nn.functional.relu(self.fc1(h))
        h = self.fc2(h)
        return self.head(h)


def test_fleet_distributed_model_trains():
    paddle.seed(9)
    model = fleet.distributed_model(_TPMLP())
    opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
        learning_rate=0.01, parameters=model.parameters()))
    rng = np.random.RandomState(2)
    ids = paddle.to_tensor(rng.randint(0, 32, (8, 6)).astype(np.int32))
    labels = paddle.to_tensor(rng.randint(0, 32, (8, 6)).astype(np.int64))
    losses = []
    for _ in range(5):
        logits = model(ids)
        loss = paddle.nn.functional.cross_entropy(
            logits.reshape([-1, 32]), labels.reshape([-1]))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_sharding_stage2():
    """DygraphShardingOptimizer shards moments + grads over sharding axis."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 8,
                               "sep_degree": 1}
    hcg_prev = fleet.get_hybrid_communicate_group()
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(3)
        net = paddle.nn.Linear(16, 16)
        inner = paddle.optimizer.Adam(learning_rate=0.01,
                                      parameters=net.parameters())
        opt = fleet.DygraphShardingOptimizer(
            inner, fleet.get_hybrid_communicate_group(), stage=2)
        x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        m = inner._accumulators["moment1"][id(net.weight)]
        assert {s.data.shape for s in m._read().addressable_shards} \
            == {(2, 16)}
        # a bias-correction power is one number: nothing to cut
        for name in ("beta1_pow", "beta2_pow"):
            for t in inner._accumulators[name].values():
                v = t._read()
                assert v.shape == () and not t.is_dist()
                assert v.sharding.is_fully_replicated
    finally:
        fleet.set_hybrid_communicate_group(hcg_prev)


def test_group_sharded_parallel_stage3():
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 8,
                               "sep_degree": 1}
    hcg_prev = fleet.get_hybrid_communicate_group()
    fleet.init(is_collective=True, strategy=strategy)
    try:
        from paddle_tpu.distributed.fleet.sharding_optimizer import \
            group_sharded_parallel
        paddle.seed(3)
        net = paddle.nn.Linear(16, 16)
        opt = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=net.parameters())
        net, opt, _ = group_sharded_parallel(net, opt, level="p_g_os")
        # params now sharded (FSDP layout)
        w = net.weight._read()
        assert {s.data.shape for s in w.addressable_shards} == {(2, 16)}
        x = paddle.to_tensor(np.random.randn(4, 16).astype(np.float32))
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
    finally:
        fleet.set_hybrid_communicate_group(hcg_prev)


def test_zero_mp_pp_1f1b_single_layout():
    """ZeRO-2 (sharding axis = batch axis) composed with Megatron TP and
    the FUSED 1F1B pipeline schedule in one device layout (VERDICT r4
    item 7; reference bar: semi_auto_llama dp+mp+pp with sharding
    stages + pipeline_parallel.py:663 train_batch)."""
    from paddle_tpu.distributed.fleet.sharding_optimizer import \
        DygraphShardingOptimizer
    from paddle_tpu.distributed.fleet.topology import \
        HybridCommunicateGroup
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    # the 64-wide toy of tests/test_scale5.py's ``_pipe_run``; two steps: the first call of a
    # ``to_static`` function runs eagerly, the second is the compiled one
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32, dropout=0.0)
    pp, shd, mp = 2, 2, 2
    hcg = HybridCommunicateGroup(dp_degree=1, pp_degree=pp,
                                 sharding_degree=shd, sep_degree=1,
                                 mp_degree=mp)
    mesh = dist.ProcessMesh(np.arange(8).reshape(pp, shd, mp),
                            ["pp", "sharding", "mp"])
    paddle.seed(0)
    model = GPTForCausalLMPipe(cfg, mesh, pp_axis="pp",
                               dp_axis="sharding", num_microbatches=2)
    model.blocks.shard(mesh, "pp", tp_axis="mp", tp_rules={
        "attn.qkv.weight": 2, "attn.qkv.bias": 1,
        "mlp.fc1.weight": 2, "mlp.fc1.bias": 1,
        "attn.proj.weight": 1, "mlp.fc2.weight": 1,
    })
    model.train()
    inner = paddle.optimizer.AdamW(learning_rate=1e-3,
                                   parameters=model.parameters())
    opt = DygraphShardingOptimizer(inner, hcg, stage=2)

    @paddle.jit.to_static
    def train_step(ids, labels):
        loss = model.train_batch(ids, labels)   # fused 1F1B
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)
    pl = [dist.Replicate(), dist.Shard(0), dist.Replicate()]
    losses = []
    for _ in range(2):
        ids = dist.shard_tensor(
            rng.integers(0, 128, (4, 16)).astype(np.int32), mesh, pl)
        labels = dist.shard_tensor(
            rng.integers(0, 128, (4, 16)).astype(np.int32), mesh, pl)
        losses.append(float(train_step(ids, labels)))
    assert all(np.isfinite(l) for l in losses), losses

    # ZeRO: moments sharded over `sharding`; TP: stacked qkv keeps mp;
    # and the stacked weights keep their pp sharding through updates
    accs = inner._accumulators["moment1"]
    assert any("sharding" in str(getattr(a._read().sharding, "spec", ""))
               for a in accs.values())
    w = model.blocks.stacked_parameter("attn.qkv.weight")._read()
    spec = str(getattr(w.sharding, "spec", ""))
    assert "mp" in spec and "pp" in spec, spec


def test_dryrun_multichip_zero_mp_pp_1f1b():
    """One of the five layouts of ``__graft_entry__.dryrun_multichip(8)``
    (tests/test_models.py holds it to them): ZeRO-2 through
    ``DygraphShardingOptimizer`` and a ``HybridCommunicateGroup`` of its
    own, whatever this module's is, x mp x pp under fused 1F1B: the layout
    of ``test_zero_mp_pp_1f1b_single_layout`` above at the entry's toy."""
    import __graft_entry__ as g
    g._force_virtual_cpu(8)
    g._dryrun_zero_mp_pp_1f1b(8)
