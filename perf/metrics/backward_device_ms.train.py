"""Device self time per step of the operations traced under
``backward`` (``run_backward``), the recomputed forward left out."""
from perf import phase_reduce


def read(run):
    return phase_reduce.device_ms(run, "backward")
