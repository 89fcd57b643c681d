"""Device self time per step of the operations traced under the
program's ``dispatch`` or ``combine`` scope in every phase (forward, recompute and
backward): a cross-cut of the four phase metrics."""
from perf import scope_readers

SCOPES = ('dispatch', 'combine')


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
