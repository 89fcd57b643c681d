"""One ``jax.jit`` trace in place of an eager run, for a case whose point
is NOT the eager tape.  Eagerly every op is a ``jax.vjp`` of its own
(ROADMAP D9): a published vision network's forward is 50-100 s, a
pipelined GPT's backward over eight devices 70 s.  Traced, the same
Python runs once over tracers, the same tape nodes are made and walked,
and XLA compiles one program: seconds.  Parameters are constants of the
trace.

No user runs framework code this way (they run it eagerly or under
``to_static``), so nothing is checked ONLY here: each mechanism keeps a
case on the eager tape beside its traced one (tests/test_pipeline_schedules
``test_1f1b_train_batch_parity`` and ``test_interleaved_forward_parity``,
tests/test_vision_models_breadth ``test_new_models_train_step``, the
``generate`` cases of tests/test_generation).  What a traced run leaves in
a layer (gradients) is a tracer: trace only layers the case made itself
and drops."""
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor


def _is_tensor(t):
    return isinstance(t, Tensor)


def call(fn, *arrays):
    """``fn(*tensors)`` under one trace: its result, every Tensor in it
    replaced by its array."""
    def run(*xs):
        out = fn(*(paddle.to_tensor(x) for x in xs))
        return jax.tree.map(lambda t: t._read() if _is_tensor(t) else t,
                            out, is_leaf=_is_tensor)

    return jax.jit(run)(*(jnp.asarray(a) for a in arrays))


def forward(model, *arrays):
    """``model(*arrays)`` in eval mode under ``no_grad``: nothing is
    written into the model."""
    model.eval()

    def run(*xs):
        with paddle.no_grad():
            return model(*xs)

    return call(run, *arrays)
