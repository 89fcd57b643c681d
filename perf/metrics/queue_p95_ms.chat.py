"""Due time to the engine step that admitted the request, 95th percentile over the requests admitted."""
from perf import readers


def read(run):
    return readers.p95(run.counters["queue_ms"])
