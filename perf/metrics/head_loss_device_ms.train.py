"""Device self time per step of the operations traced under ``lm_head``
or ``loss`` in any phase: a cross-cut of the four phase metrics, not a
fifth part of the step."""
from perf import phase_reduce


def read(run):
    t = phase_reduce.table(run)
    if t is None or not t.calls:
        return None
    return t.head_loss_ns() / t.calls / 1e6
