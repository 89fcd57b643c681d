"""How late after its due time the open-loop generator sent a request, 95th percentile."""
from perf import readers


def read(run):
    return readers.p95(run.counters["late_ms"])
