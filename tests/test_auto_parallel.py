"""Semi-auto (GSPMD) API tests: shard_tensor/reshard/shard_layer/
shard_optimizer + a 2-D dp×mp MLP trained on the virtual mesh with
sharding asserted (VERDICT round-1 item 3; reference pattern
test/auto_parallel/semi_auto_parallel_simple_net_dp_mp_pp.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist


@pytest.fixture
def mesh2d():
    return dist.ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])


def _device_count_of(t):
    return len(t._read().sharding.device_set)


def test_shard_tensor_basic(mesh2d):
    x = paddle.to_tensor(np.random.randn(8, 6).astype(np.float32))
    d = dist.shard_tensor(x, mesh2d, [dist.Shard(0), dist.Replicate()])
    assert d.is_dist()
    assert d.process_mesh is mesh2d
    assert d.placements[0] == dist.Shard(0)
    np.testing.assert_allclose(d.numpy(), x.numpy())
    # sharded over 4-way dp on dim 0: addressable shards are [2, 6]
    shard_shapes = {s.data.shape for s in d._read().addressable_shards}
    assert shard_shapes == {(2, 6)}


def test_shard_tensor_2d(mesh2d):
    x = paddle.to_tensor(np.random.randn(8, 6).astype(np.float32))
    d = dist.shard_tensor(x, mesh2d, [dist.Shard(0), dist.Shard(1)])
    shard_shapes = {s.data.shape for s in d._read().addressable_shards}
    assert shard_shapes == {(2, 3)}


def test_reshard(mesh2d):
    x = paddle.to_tensor(np.random.randn(8, 6).astype(np.float32))
    d = dist.shard_tensor(x, mesh2d, [dist.Shard(0), dist.Replicate()])
    r = dist.reshard(d, mesh2d, [dist.Replicate(), dist.Shard(1)])
    np.testing.assert_allclose(r.numpy(), x.numpy())
    shard_shapes = {s.data.shape for s in r._read().addressable_shards}
    assert shard_shapes == {(8, 3)}
    assert r.placements[1] == dist.Shard(1)


def test_reshard_differentiable(mesh2d):
    x = paddle.to_tensor(np.random.randn(8, 6).astype(np.float32),
                         stop_gradient=False)
    d = dist.reshard(x, mesh2d, [dist.Shard(0)])
    loss = (d * d).sum()
    loss.backward()
    np.testing.assert_allclose(x.grad.numpy(), 2 * x.numpy(), rtol=1e-5)


def test_dtensor_from_fn(mesh2d):
    d = dist.dtensor_from_fn(paddle.ones, mesh2d, [dist.Replicate()], [4, 4])
    assert d.is_dist()
    np.testing.assert_allclose(d.numpy(), np.ones((4, 4)))


def test_partial_is_metadata(mesh2d):
    x = paddle.to_tensor(np.random.randn(4, 4).astype(np.float32))
    d = dist.shard_tensor(x, mesh2d, [dist.Partial(), dist.Replicate()])
    assert d.placements[0].is_partial()
    r = dist.reshard(d, mesh2d, [dist.Replicate(), dist.Replicate()])
    np.testing.assert_allclose(r.numpy(), x.numpy())


class _MLP(paddle.nn.Layer):
    def __init__(self, din=8, dh=32, dout=4):
        super().__init__()
        self.fc1 = paddle.nn.Linear(din, dh)
        self.fc2 = paddle.nn.Linear(dh, dout)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


def _mp_shard_fn(name, sub, mesh):
    """Megatron split: fc1 column-parallel, fc2 row-parallel over mp."""
    if name.endswith("fc1"):
        dist.shard_parameter(sub.weight, mesh,
                             [dist.Replicate(), dist.Shard(1)])
        dist.shard_parameter(sub.bias, mesh,
                             [dist.Replicate(), dist.Shard(0)])
    elif name.endswith("fc2"):
        dist.shard_parameter(sub.weight, mesh,
                             [dist.Replicate(), dist.Shard(0)])


def test_shard_layer_and_train_dp_mp(mesh2d):
    """2-D dp×mp training parity vs single-device, shardings asserted."""
    paddle.seed(11)
    ref = _MLP()
    paddle.seed(11)
    net = _MLP()
    dist.shard_layer(net, mesh2d, _mp_shard_fn)

    # weight shardings took effect
    w1 = net.fc1.weight._read()
    assert {s.data.shape for s in w1.addressable_shards} == {(8, 16)}
    w2 = net.fc2.weight._read()
    assert {s.data.shape for s in w2.addressable_shards} == {(16, 4)}

    opt_ref = paddle.optimizer.Adam(learning_rate=0.01,
                                    parameters=ref.parameters())
    opt = dist.shard_optimizer(paddle.optimizer.Adam(
        learning_rate=0.01, parameters=net.parameters()))

    rng = np.random.RandomState(3)
    for step in range(3):
        xb = rng.randn(16, 8).astype(np.float32)
        yb = rng.randn(16, 4).astype(np.float32)

        x = paddle.to_tensor(xb)
        y = paddle.to_tensor(yb)
        l_ref = ((ref(x) - y) ** 2).mean()
        l_ref.backward()
        opt_ref.step()
        opt_ref.clear_grad()

        xd = dist.shard_tensor(paddle.to_tensor(xb), mesh2d, [dist.Shard(0)])
        y = paddle.to_tensor(yb)
        l = ((net(xd) - y) ** 2).mean()
        l.backward()
        opt.step()
        opt.clear_grad()

        np.testing.assert_allclose(float(l_ref), float(l), rtol=1e-4)

    # weights stayed in sync across the two runs
    np.testing.assert_allclose(net.fc1.weight.numpy(),
                               ref.fc1.weight.numpy(), rtol=1e-4)


def test_shard_optimizer_zero1(mesh2d):
    """shard_fn puts moments sharded over dp — ZeRO-1 layout."""
    net = _MLP()
    dist.shard_layer(net, mesh2d)

    asked = set()

    def moment_shard(acc_name, param, acc):
        asked.add(acc_name)
        if param.shape[0] % 4 == 0:
            return [dist.Shard(0), dist.Replicate()]
        return None

    opt = dist.shard_optimizer(
        paddle.optimizer.Adam(learning_rate=0.01,
                              parameters=net.parameters()),
        shard_fn=moment_shard)
    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    loss = net(x).sum()
    loss.backward()
    opt.step()
    m = opt._inner._accumulators["moment1"][id(net.fc1.weight)]
    assert {s.data.shape for s in m._read().addressable_shards} == {(2, 32)}
    # a bias-correction power is one number: shard_fn is not asked to
    # place it and it stays replicated
    assert asked == {"moment1", "moment2"}
    for name in ("beta1_pow", "beta2_pow"):
        for t in opt._inner._accumulators[name].values():
            assert t._read().shape == () and not t.is_dist()


def test_to_static_sharded_step(mesh2d):
    """A sharded train step compiles to ONE SPMD program via jit capture."""
    paddle.seed(5)
    net = _MLP()
    dist.shard_layer(net, mesh2d, _mp_shard_fn)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=net.parameters())

    @paddle.jit.to_static
    def step(x, y):
        loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # eager twin for parity
    paddle.seed(5)
    ref = _MLP()
    opt_ref = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=ref.parameters())

    rng = np.random.RandomState(1)
    xb = rng.randn(16, 8).astype(np.float32)
    yb = rng.randn(16, 4).astype(np.float32)
    losses, ref_losses = [], []
    for _ in range(4):
        xd = dist.shard_tensor(paddle.to_tensor(xb), mesh2d,
                               [dist.Shard(0)])
        y = paddle.to_tensor(yb)
        losses.append(float(step(xd, y)))

        x = paddle.to_tensor(xb)
        y = paddle.to_tensor(yb)
        l_ref = ((ref(x) - y) ** 2).mean()
        l_ref.backward()
        opt_ref.step()
        opt_ref.clear_grad()
        ref_losses.append(float(l_ref))

    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    assert losses[-1] < losses[0]  # fixed batch: SGD must make progress
    # weight sharding preserved through compiled steps
    w1 = net.fc1.weight._read()
    assert {s.data.shape for s in w1.addressable_shards} == {(8, 16)}


def test_dryrun_multichip_hybrid():
    """One of the five layouts of ``__graft_entry__.dryrun_multichip(8)``
    (tests/test_models.py holds it to them): dp x mp x sp with ring
    attention, one compiled sharded step as the case above."""
    import __graft_entry__ as g
    g._force_virtual_cpu(8)
    g._dryrun_hybrid(8)
