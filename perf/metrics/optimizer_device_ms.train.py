"""Device self time per step of the operations traced under
``optimizer`` (``Optimizer.step``, its gradient clip included) or
``clear_grad``."""
from perf import phase_reduce


def read(run):
    return phase_reduce.device_ms(run, "optimizer")
