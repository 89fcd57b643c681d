"""Share of the window's untraced stretch that its blocks took over
1.10 x their neighbours' pace, from the program's own log of calls and
blocking reads (``perf/window_log.py``): a quiet window reads 0.  The
note ``stall_blocks`` says, for each block that ran long, in which
host phase the seconds lie (``device``: the host waited in the read)."""
from perf import window_log


def read(run):
    stretch = window_log.of(run)
    if stretch is None:
        return None
    value = window_log.host_stall_share(stretch)
    if value is not None:
        run.note(stall_blocks=window_log.stall_blocks(stretch))
    return value
