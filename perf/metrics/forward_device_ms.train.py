"""Device self time per step of the operations traced under a layer's
scope in the forward pass: under neither ``backward`` nor a remat
component, ``optimizer`` nor ``clear_grad``."""
from perf import phase_reduce


def read(run):
    return phase_reduce.device_ms(run, "forward")
