"""Device self time per step of the operations traced under the
program's ``full_attention`` scope in every phase (forward, recompute
and backward): the full layers' q, k, v projections (``qkv``), rotation
by the YaRN table (``rope``), the two flash kernels and ``o_proj``.  A
cross-cut of the four phase metrics."""
from perf import scope_readers

SCOPES = ('full_attention',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
