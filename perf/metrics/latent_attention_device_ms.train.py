"""Device self time per step of the operations traced under the
program's ``latent_attention`` scope in every phase (forward, recompute
and backward): the projections down and up, the latent's norm, the
rotation, the assembly of the kernel's operands, the flash kernels and
the output projection.  A cross-cut of the four phase metrics."""
from perf import scope_readers

SCOPES = ('latent_attention',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
