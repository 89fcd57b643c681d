"""Device self time per step of the operations traced under the
program's ``out_gate`` scope in every phase (forward, recompute and
backward): the per-head output gate of an attention layer, its
projection (hidden -> heads), the sigmoid and the product with the
attention's result.  A cross-cut of the four phase metrics, inside
``window_attention_device_ms.train`` and ``full_attention_device_ms.train``."""
from perf import scope_readers

SCOPES = ('out_gate',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
