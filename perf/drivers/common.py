"""What the drivers share: the record a run hands back, and the traced
stretch of a window."""
from __future__ import annotations

import json
import time


class Run:
    """What a driver measured.  ``end_to_end`` maps a metric's name to
    its value; the per-layer readers are handed this whole record
    (``trace``, ``counters``, ``ctx``)."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.correct = False
        self.attempted = 0
        self.failed = 0
        self.end_to_end = {}
        self.counters = {}
        self.notes = []
        self.trace = None

    def note(self, **kw):
        self.notes.append(json.dumps(kw))


def setup_seconds(ctx, window_start, reference_s):
    """Process start to the first timed step, without the reference's
    time (the check is not set-up)."""
    return window_start - ctx.t_start - reference_s


class TracedStretch:
    """Profiles the first ``traffic['trace']['seconds']`` of a window.
    The profiler is started during set-up (``start``: it stalls the
    host for seconds), and only the window's spans carry the harness's
    names, so the reduction sees the window alone.  ``poll`` is called
    at points where the device has just been read back."""

    def __init__(self, ctx, spans):
        self.on = ctx.trace
        self.length = ctx.traffic["trace"]["seconds"]
        self.dir, self.spans = ctx.trace_dir, spans
        self.started = self.stopped = None

    def start(self):
        if self.on:
            import jax
            jax.profiler.start_trace(self.dir)
            self.started = time.perf_counter()

    def poll(self, since_window_start):
        if self.on and self.stopped is None \
                and since_window_start >= self.length:
            import jax
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()

    def finish(self):
        """Stop if still running; reduce the trace."""
        if not self.on:
            return None
        import jax

        from perf import trace_reduce
        if self.stopped is None:
            jax.profiler.stop_trace()
            self.stopped = time.perf_counter()
        path = trace_reduce.find_xplane(self.dir)
        return trace_reduce.Trace(trace_reduce.load_xplane(path, self.spans))
