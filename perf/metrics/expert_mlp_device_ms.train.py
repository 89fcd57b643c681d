"""Device self time per step of the held experts' products in every
phase (forward, the two recomputes and backward): the operations
traced under the program's ``expert_mlp`` scope, and the grouped-product
kernels the chip's compiler makes of ``ragged_dot`` (named
``%ragged-dot-none``, with no scope of the program's)."""
from perf import scope_readers


def read(run):
    return scope_readers.device_ms_under(run, **scope_readers.EXPERT_MLP)
