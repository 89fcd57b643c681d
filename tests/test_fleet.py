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
