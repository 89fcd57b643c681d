"""1F1B and interleaved-VPP pipeline schedules (SURVEY D15; reference
pipeline_parallel.py:663 train_batch 1F1B, :912 interleaved). Parity model:
same outputs/grads/losses as the identical weights run sequentially.  The
same schedule under ``GPTForCausalLMPipe`` is
tests/test_pipeline_schedules_gpt.py."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.fleet.pipeline import PipelinedBlocks


@pytest.fixture(scope="module")
def mesh():
    return dist.ProcessMesh(np.arange(8).reshape(4, 2), ["pp", "dp"])


class Block(nn.Layer):
    def __init__(self, width=16):
        super().__init__()
        self.fc1 = nn.Linear(width, 2 * width)
        self.fc2 = nn.Linear(2 * width, width)

    def forward(self, x):
        return x + self.fc2(F.gelu(self.fc1(x)))


def _eager_clone(pipe, n_blocks):
    blocks = [Block() for _ in range(n_blocks)]
    names = [n for n, _ in blocks[0].named_parameters()]
    for n in names:
        vals = pipe.layer_values(n)
        for li, b in enumerate(blocks):
            dict(b.named_parameters())[n]._write(vals[li])
    return blocks


def test_interleaved_forward_parity(mesh):
    """VPP (interleave=2) computes the same function as sequential."""
    paddle.seed(0)
    pipe = PipelinedBlocks(Block, 8, mesh=mesh, pp_axis="pp",
                           num_microbatches=4, interleave=2)
    # storage order is the round-robin chunk permutation, not identity
    assert not np.array_equal(pipe.layer_order, np.arange(8))
    x = np.random.default_rng(0).normal(size=(8, 4, 16)).astype("float32")

    xt = paddle.to_tensor(x)
    xt.stop_gradient = False
    out = pipe(xt, batch_axes="dp")
    out.sum().backward()

    blocks = _eager_clone(pipe, 8)
    ref = paddle.to_tensor(x)
    ref.stop_gradient = False
    h = ref
    for b in blocks:
        h = b(h)
    h.sum().backward()

    np.testing.assert_allclose(np.asarray(out._read()),
                               np.asarray(h._read()), atol=1e-5)
    np.testing.assert_allclose(np.asarray(xt.grad._read()),
                               np.asarray(ref.grad._read()), atol=1e-5)
    # stacked grads match the eager per-layer grads through layer_values
    # ordering: compare via the inverse permutation
    for n in dict(blocks[0].named_parameters()):
        gs = np.asarray(pipe.stacked_parameter(n).grad._read())
        inv = np.argsort(pipe.layer_order)
        ge = np.stack([np.asarray(dict(b.named_parameters())[n]
                                  .grad._read()) for b in blocks])
        np.testing.assert_allclose(gs, ge[pipe.layer_order], atol=1e-4)


@pytest.mark.parametrize("M", [2, 4, 6, 8])
def test_interleaved_any_microbatch_count(mesh, M):
    """Banking must cover M < pp, M == pp, partial and full groups (the
    scan-length boundary: v*M + pp ticks only suffices when pp | M)."""
    paddle.seed(3)
    pipe = PipelinedBlocks(Block, 8, mesh=mesh, pp_axis="pp",
                           num_microbatches=M, interleave=2)
    x = np.random.default_rng(3).normal(size=(M * 2, 2, 16)) \
        .astype("float32")
    out = pipe(paddle.to_tensor(x), batch_axes="dp")

    blocks = _eager_clone(pipe, 8)
    h = paddle.to_tensor(x)
    for b in blocks:
        h = b(h)
    np.testing.assert_allclose(np.asarray(out._read()),
                               np.asarray(h._read()), atol=1e-5)


def test_interleaved_requires_divisibility(mesh):
    with pytest.raises(ValueError):
        PipelinedBlocks(Block, 6, mesh=mesh, pp_axis="pp", interleave=2)
    with pytest.raises(ValueError):
        PipelinedBlocks(Block, 8, interleave=2)  # mesh required


def test_1f1b_train_batch_parity(mesh):
    """Fused 1F1B loss + grads == sequential fwd/bwd with the same
    weights (the reference's hybrid_parallel_pp loss-parity pattern)."""
    paddle.seed(1)
    M = 4
    pipe = PipelinedBlocks(Block, 4, mesh=mesh, pp_axis="pp",
                           num_microbatches=M)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(8, 4, 16)).astype("float32")
    y = rng.normal(size=(8, 4, 16)).astype("float32")

    def loss_fn(out, tgt):
        return ((out - tgt) ** 2).mean()

    xt = paddle.to_tensor(x)
    xt.stop_gradient = False
    loss = pipe.train_batch(xt, paddle.to_tensor(y), loss_fn,
                            batch_axes="dp")
    loss.backward()

    # sequential reference: same weights, same per-microbatch mean loss
    blocks = _eager_clone(pipe, 4)
    ref = paddle.to_tensor(x)
    ref.stop_gradient = False
    h = ref
    for b in blocks:
        h = b(h)
    # microbatch mean-of-means == full-batch mean here (equal mb sizes)
    ref_loss = ((h - paddle.to_tensor(y)) ** 2).mean()
    ref_loss.backward()

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(xt.grad._read()),
                               np.asarray(ref.grad._read()), atol=1e-5)
    for n in dict(blocks[0].named_parameters()):
        gs = np.asarray(pipe.stacked_parameter(n).grad._read())
        ge = np.stack([np.asarray(dict(b.named_parameters())[n]
                                  .grad._read()) for b in blocks])
        np.testing.assert_allclose(gs, ge, atol=1e-4)


def test_1f1b_trains_under_jit(mesh):
    """jit-compiled 1F1B train step drives the loss down."""
    paddle.seed(2)
    pipe = PipelinedBlocks(Block, 4, mesh=mesh, pp_axis="pp",
                           num_microbatches=2)
    opt = paddle.optimizer.SGD(learning_rate=0.05,
                               parameters=pipe.parameters())
    rng = np.random.default_rng(2)
    x = paddle.to_tensor(rng.normal(size=(4, 2, 16)).astype("float32"))
    y = paddle.to_tensor(rng.normal(size=(4, 2, 16)).astype("float32") * .1)

    def loss_fn(out, tgt):
        return ((out - tgt) ** 2).mean()

    @paddle.jit.to_static
    def step(x, y):
        loss = pipe.train_batch(x, y, loss_fn, batch_axes="dp")
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    losses = [float(step(x, y)) for _ in range(6)]
    assert losses[-1] < losses[0] * 0.9, losses
