"""Share of the window's untraced stretch in which the host was NOT
waiting in a blocking read of a device value (the program's read log,
``perf/window_log.py``).  Near 100 the host sets the pace; a few per
cent is a host that launches and then waits for the chip."""
from perf import window_log


def read(run):
    stretch = window_log.of(run)
    return None if stretch is None else window_log.host_busy_share(stretch)
