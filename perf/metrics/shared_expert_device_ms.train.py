"""Device self time per step of the operations traced under the
program's ``shared_expert`` scope in every phase: the one SwiGLU every
token passes beside the routed experts."""
from perf import scope_readers

SCOPES = ('shared_expert',)


def read(run):
    return scope_readers.device_ms_under(run, SCOPES)
