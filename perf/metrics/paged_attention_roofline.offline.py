"""Bandwidth bound of the resident KV over the device time of ragged_paged_attention."""
from perf import readers


def read(run):
    return readers.paged_attention_roofline(run)
