"""Device-busy time per training step in the traced window."""
from perf import readers


def read(run):
    return readers.device_ms_per_span(run, "train_step")
