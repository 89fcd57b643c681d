"""100 x self time of the window's device operations that carry no
program scope / device-busy time: how much of the step the program's
phase scopes do not name."""
from perf import phase_reduce


def read(run):
    t = phase_reduce.table(run)
    if t is None or not t.busy_ns:
        return None
    return 100.0 * t.phase_ns(phase_reduce.UNATTRIBUTED) / t.busy_ns
