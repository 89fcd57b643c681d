"""Device self time per step of the routed block whole, in every phase:
the operations traced under the program's ``router``, ``dispatch``,
``combine`` and ``expert_mlp`` scopes, and the grouped-product kernels
the chip's compiler makes of ``ragged_dot`` (``%ragged-dot-none``, with
no scope of the program's)."""
from perf import scope_readers

SCOPES = ('router', 'dispatch', 'combine')


def read(run):
    mlp = scope_readers.EXPERT_MLP
    return scope_readers.device_ms_under(
        run, SCOPES + mlp["scopes"], mlp["kernels"])
