"""Host time per compiled-step call of reading the step's arguments and
captured state and of writing its results back (the program's
``to_static.read_state`` + ``to_static.write_state`` spans)."""
from perf import phase_reduce


def read(run):
    t = phase_reduce.spans_of(run)
    if t is None or not t.calls:
        return None
    return (t.span_ns("to_static.read_state")
            + t.span_ns("to_static.write_state")) / t.calls / 1e6
