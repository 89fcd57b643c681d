"""Host time from a host batch to the device tensors the step is given
(the harness's ``input_feed`` span), mean over the traced window."""
from perf import readers


def read(run):
    return readers.span_mean_ms(run, "input_feed")
