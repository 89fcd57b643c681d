"""Host time of the compiled step's launch alone (the program's
``to_static.launch`` span), mean over the traced window."""
from perf import phase_reduce


def read(run):
    t = phase_reduce.spans_of(run)
    if t is None or not t.spans["to_static.launch"]:
        return None
    return t.span_ns("to_static.launch") \
        / len(t.spans["to_static.launch"]) / 1e6
