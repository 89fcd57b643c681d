"""Device self time per step of the operations traced under the
program's ``window_attention`` scope in every phase (forward, recompute
and backward): the sliding-window layers' q, k, v projections
(``qkv``), rotation (``rope``), the two window kernels and ``o_proj``.
A cross-cut of the four phase metrics."""
from perf import scope_readers

SCOPES = ('window_attention',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
