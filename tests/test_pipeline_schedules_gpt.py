"""The fused 1F1B schedule under ``GPTForCausalLMPipe`` (tied embeddings,
the epilogue inside the schedule): tests/test_pipeline_schedules.py has the
schedules themselves on plain blocks; the halves share the mesh's shape
only, and together they were over 200 s of tier-1."""
import numpy as np
import pytest

import _traced
import paddle_tpu as paddle
import paddle_tpu.distributed as dist


@pytest.fixture(scope="module")
def mesh():
    return dist.ProcessMesh(np.arange(8).reshape(4, 2), ["pp", "dp"])


def test_gpt_pipe_1f1b_train_batch_parity(mesh):
    """GPT 1F1B train_batch (epilogue inside the schedule via post_params,
    tied embeddings getting BOTH grad paths) matches the plain GPT."""
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTForCausalLMPipe)

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_heads=4, max_seq_len=16, dropout=0.0)
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 64, (4, 16)).astype(np.int32)
    labels = rng.integers(0, 64, (4, 16)).astype(np.int32)

    paddle.seed(0)
    pipe = GPTForCausalLMPipe(cfg, mesh, pp_axis="pp", dp_axis="dp",
                              num_microbatches=2)
    paddle.seed(0)
    ref = GPTForCausalLM(cfg)
    ref.gpt.wte.weight._write(pipe.wte.weight._read())
    ref.gpt.wpe.weight._write(pipe.wpe.weight._read())
    ref.gpt.ln_f.weight._write(pipe.ln_f.weight._read())
    ref.gpt.ln_f.bias._write(pipe.ln_f.bias._read())
    for li, blk in enumerate(ref.gpt.blocks):
        for n, p in blk.named_parameters():
            p._write(pipe.blocks.stacked_parameter(n)._read()[li])

    names = [n for n, _ in ref.gpt.blocks[0].named_parameters()]

    # one trace of both sides and not op by op (76 s here at PR 40):
    # what this case adds to ``test_1f1b_train_batch_parity``, which
    # walks the eager tape of the same schedule, is the GPT epilogue
    def both(ids, labels):
        loss = pipe.train_batch(ids, labels)
        loss.backward()
        ref_loss = ref(ids, labels)
        ref_loss.backward()
        return (loss, ref_loss,
                [(pipe.wte.weight.grad, ref.gpt.wte.weight.grad),
                 (pipe.ln_f.weight.grad, ref.gpt.ln_f.weight.grad)],
                {n: pipe.blocks.stacked_parameter(n).grad for n in names},
                {n: [dict(b.named_parameters())[n].grad
                     for b in ref.gpt.blocks] for n in names})

    loss, ref_loss, tied_and_norm, gs, ge = _traced.call(both, ids, labels)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4)
    # tied embedding grad = embedding path + head path; then ln_f's
    for got, want in tied_and_norm:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-4)
    for n in names:
        np.testing.assert_allclose(np.asarray(gs[n]), np.stack(ge[n]),
                                   atol=2e-4)


def test_gpt_pipe_1f1b_trains(mesh):
    """jit-compiled GPT 1F1B steps drive the loss down."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLMPipe

    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_heads=4, max_seq_len=16, dropout=0.0)
    paddle.seed(1)
    pipe = GPTForCausalLMPipe(cfg, mesh, pp_axis="pp", dp_axis="dp",
                              num_microbatches=2)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=pipe.parameters())
    rng = np.random.default_rng(6)
    ids = paddle.to_tensor(rng.integers(0, 64, (4, 16)).astype(np.int32))
    labels = paddle.to_tensor(rng.integers(0, 64, (4, 16)).astype(np.int32))

    @paddle.jit.to_static
    def step(i, l):
        loss = pipe.train_batch(i, l)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    losses = [float(step(ids, labels)) for _ in range(6)]
    assert losses[-1] < losses[0], losses
