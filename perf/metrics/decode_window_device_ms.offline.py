"""Device-busy time of one decode-window program, mean over the traced windows."""
from perf import readers


def read(run):
    return readers.step_device_ms(run, "window")
