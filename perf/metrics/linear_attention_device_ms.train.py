"""Device self time per step of the operations traced under the
program's ``linear_attention`` scope in every phase (forward, recompute
and backward): the three projections with their convolutions and SiLU,
the decay gate, the chunk kernels with the normalisation, running sums
and folding round them, the gated head norm and the output projection.
A cross-cut of the four phase metrics."""
from perf import scope_readers

SCOPES = ('linear_attention',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
