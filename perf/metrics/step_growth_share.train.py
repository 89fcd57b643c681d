"""How much the step grows inside a window: the median pace of the
last quarter of the untraced stretch's blocks over that of the first
quarter, less 1 (``perf/window_log.py``; a block's pace is the time
between two loss readbacks over the calls made between them).  A
dense model reads 0; a sparse one whose routed share drifts reads its
growth."""
from perf import window_log


def read(run):
    stretch = window_log.of(run)
    if stretch is None:
        return None
    run.note(window_pace_ms=[round(b["pace_ms"], 3) for b in stretch],
             window_block_calls=[b["calls"] for b in stretch])
    return window_log.step_growth_share(stretch)
