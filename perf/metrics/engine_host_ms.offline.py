"""Host time of one engine step less the device-busy time inside it, mean over the traced steps."""
from perf import readers


def read(run):
    return readers.engine_host_ms(run)
