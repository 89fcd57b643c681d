"""Host time of one ``train_step`` call until it returns (the span the
harness puts round it), mean over the traced window."""
from perf import readers


def read(run):
    return readers.span_mean_ms(run, "train_step")
