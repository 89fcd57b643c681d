"""ISSUE 27: where the tape linearises an op.

Under a ``jit.to_static`` capture ``core/dispatch.py`` builds each
op's ``jax.vjp`` as the op is recorded, so the program holds every
forward once; in eager the linearisation waits for the backward.

* the flash kernel's forward is traced once a layer, with recompute
  and without (the parent traced it twice);
* the capture's tally (``core/scope.TapeCounts``) says so (its way
  onto the ``compile`` span: ``tests/test_phase_scopes.py``);
* a captured step gives the eager step's losses and gradients over
  recompute policies, a BERT layer, a tensor hook, ``retain_graph``,
  double backward and a forward that never backwards.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.core import scope
from paddle_tpu.models.bert import BertConfig, BertLayer
from paddle_tpu.models.gpt import GPTBlock, GPTConfig, GPTForCausalLM
from paddle_tpu.nn.functional import attention

N_LAYER = 2


def _pallas_calls(jaxpr, out):
    """Names of the ``pallas_call``s of a jaxpr, sub-jaxprs included
    (checkpoint, custom_vjp and pjit bodies)."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


@pytest.mark.parametrize("use_recompute", [True, False],
                         ids=["recompute", "no_recompute"])
def test_flash_forward_is_traced_once_a_layer(monkeypatch, use_recompute):
    # off the TPU the model takes the XLA attention: steer it to the
    # kernel (interpret mode) here, not through an option of the program
    monkeypatch.setattr(attention, "_use_pallas", lambda q: True)
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=N_LAYER, num_heads=2,
        max_seq_len=16, intermediate_size=64, dropout=0.0,
        recompute=use_recompute,
        recompute_policy="dots_and_kernels_saveable",
        use_flash_attention=True))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())

    @paddle.jit.to_static
    def train_step(ids, labels):
        loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 128, (2, 16)).astype("int32"))
    assert np.isfinite(float(train_step(ids, ids)))
    exe = train_step.concrete_program(ids, ids)
    vals = [t._data for t in [ids, ids] + exe.capt_state]
    traces = exe.trace_count
    names = _pallas_calls(exe.compiled.trace(*vals).jaxpr.jaxpr, [])
    assert exe.trace_count == traces     # jit's own trace cache answered
    assert names.count("flash_attention_fwd") == N_LAYER, names
    assert names.count("flash_attention_bwd") == N_LAYER, names
    assert exe.tape_nodes.record > 0 and exe.tape_nodes.backward == 0


# ---------------------------------------------------------------------
# captured == eager.  A case builds (params, fn): ``fn(x)`` runs its
# forward and whatever backward it has and returns the tensors to
# compare; every parameter's gradient is compared too (``_step``).
def _x(shape=(2, 8, 16), seed=0):
    return paddle.to_tensor(np.random.default_rng(seed).normal(
        size=shape).astype("float32"))


def _gpt_block(policy):
    block = GPTBlock(GPTConfig(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
        max_seq_len=8, intermediate_size=32, dropout=0.0,
        use_flash_attention=False, recompute=policy is not None,
        recompute_policy=policy or "full"))
    block.train()       # the block recomputes only while training

    def fn(x):
        out = block(x)
        loss = (out * out).mean()
        loss.backward()
        return [loss]

    return block.parameters(), fn


def _bert_layer():
    layer = BertLayer(BertConfig(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, max_seq_len=8, dropout=0.0,
        use_flash_attention=False))
    layer.train()

    def fn(x):
        loss = (layer(x) ** 2).mean()
        loss.backward()
        return [loss]

    return layer.parameters(), fn


def _tensor_hook():
    lin1, lin2 = paddle.nn.Linear(16, 16), paddle.nn.Linear(16, 4)

    def fn(x):
        h = F.gelu(lin1(x))
        h.register_hook(lambda g: g * 3.0)
        loss = lin2(h).mean()
        loss.backward()
        return [loss]

    return lin1.parameters() + lin2.parameters(), fn


def _retain_graph_twice():
    lin = paddle.nn.Linear(16, 4)

    def fn(x):
        loss = (paddle.tanh(lin(x)) ** 2).mean()
        loss.backward(retain_graph=True)
        loss.backward()     # accumulates: the gradient twice over
        return [loss]

    return lin.parameters(), fn


def _double_backward():
    lin = paddle.nn.Linear(16, 1)

    def fn(x):
        x = x * 1.0
        x.stop_gradient = False
        y = (paddle.tanh(lin(x)) ** 2).sum()
        (gx,) = paddle.grad(y, x, create_graph=True)
        penalty = (gx * gx).sum()
        penalty.backward()
        return [y, penalty, gx]

    return lin.parameters(), fn


def _never_backward():
    lin = paddle.nn.Linear(16, 4)

    def fn(x):
        return [(lin(x) ** 2).mean()]    # grad-enabled, nodes recorded

    return lin.parameters(), fn


CASES = {
    "gpt_block_recompute_full": lambda: _gpt_block("full"),
    "gpt_block_recompute_dots_and_kernels":
        lambda: _gpt_block("dots_and_kernels_saveable"),
    "gpt_block_no_recompute": lambda: _gpt_block(None),
    "bert_layer": _bert_layer,
    "tensor_hook": _tensor_hook,
    "retain_graph_twice": _retain_graph_twice,
    "double_backward": _double_backward,
    "never_backward": _never_backward,
}


def _step(params, fn):
    """``fn`` as a step that can be captured: the gradients it left
    are returned after its own outputs and cleared inside (a gradient
    that outlives the call cannot compile)."""
    def step(x):
        outs = list(fn(x))
        grads = [p.grad for p in params if p.grad is not None]
        for p in params:
            p.clear_grad()
        return outs + grads
    return step


def _numpy(tensors):
    return [np.asarray(t._read()).copy() for t in tensors]


@pytest.mark.parametrize("case", sorted(CASES))
def test_captured_step_equals_eager(case):
    paddle.seed(0)
    params, fn = CASES[case]()
    step, x = _step(params, fn), _x()
    eager = scope.tape()
    recorded = eager.record
    want = _numpy(step(x))
    assert eager.record == recorded     # eager never linearises early
    n_outs = len(fn(x))
    for p in params:
        p.clear_grad()
    # every parameter has a gradient, or (no backward) none has
    assert len(want) - n_outs == (0 if case == "never_backward"
                                  else len(params))

    static = paddle.jit.to_static(step)
    static(x)                           # discovery: eager, then captured
    got = _numpy(static(x))             # the program
    exe = static.concrete_program(x)
    assert exe is not None and exe.tape_nodes.record > 0
    # only a double backward re-linearises, by its nature
    assert (exe.tape_nodes.backward > 0) == (case == "double_backward")

    assert len(got) == len(want)
    names = ["out"] * n_outs + [p.name for p in params]
    for name, g, w in zip(names, got, want):
        np.testing.assert_allclose(g, w, rtol=2e-3, atol=1e-6,
                                   err_msg=name)
