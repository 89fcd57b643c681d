"""Device-busy time of one mixed (prefill + decode) program, mean over the traced mixed steps."""
from perf import readers


def read(run):
    return readers.step_device_ms(run, "mixed")
