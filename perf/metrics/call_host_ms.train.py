"""Host time of one compiled-step call, from ``StaticFunction.__call__``
entered to ``write_state`` returned: the median over the calls of the
window's untraced stretch of the program's own four phases (``lookup``,
``read_state``, ``launch``, ``write_state``: the call log,
``perf/window_log.py``).  ``dispatch_ms.train`` taken where the work
happens, with no profiler running."""
from perf import window_log


def read(run):
    stretch = window_log.of(run)
    phases = None if stretch is None else window_log.call_phase_ms(stretch)
    if phases is None:
        return None
    run.note(call_phase_ms=phases)
    return phases["call"]
