"""From a profiler trace to the numbers the per-layer readers use:
device-busy union, idle gaps named by the harness span that covered
them, per-op self time, kernels by name.

``load_xplane`` turns a ``.xplane.pb`` into plain lists ("raw": planes
-> lines -> events), which is also the form of the small recorded trace
the tests reduce; everything after it is arithmetic on those lists.
Times are nanoseconds on the profiler's clock, on which the host's
``TraceAnnotation`` spans and the device's operations both lie.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(hlo_text):
    """A device event is named by its whole HLO instruction; keep the
    instruction's name and the type of its (first) result:
    ``%fusion.14 bf16[50304,1024]``.  A Pallas kernel's instruction is
    named after the kernel (``%flash_attention_fwd.24``)."""
    name, _, rest = hlo_text.partition(" = ")
    result = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{name} {result.group(1)}" if result else name


def load_xplane(path, span_names):
    """The device planes' operation lines and the host events named in
    ``span_names``, as plain lists of [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    raw = {"planes": []}
    for plane in data.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        if not is_dev and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if is_dev and line.name != OPS_LINE:
                continue
            if is_dev:
                events = [[short_name(ev.name), ev.start_ns, ev.duration_ns]
                          for ev in line.events]
            else:
                events = [[ev.name, ev.start_ns, ev.duration_ns]
                          for ev in line.events if ev.name in span_names]
            if events:
                lines.append({"name": line.name, "events": events})
        raw["planes"].append({"name": plane.name, "lines": lines})
    return raw


# ------------------------------------------------------------ arithmetic
def device_ops(raw):
    """{chip index: [(name, start, end)]} sorted by start, an enclosing
    operation before its children."""
    out = {}
    for plane in raw["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        ops = []
        for line in plane["lines"]:
            ops += [(n, s, s + d) for n, s, d in line["events"]]
        ops.sort(key=lambda o: (o[1], -o[2]))
        out[int(m.group(1))] = ops
    return out


def host_spans(raw):
    """[(name, start, end)] of the harness's spans, sorted by start."""
    out = []
    for plane in raw["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [(n, s, s + d) for n, s, d in line["events"]]
    return sorted(out, key=lambda s: s[1])


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy_ns(intervals, lo, hi):
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def idle_gaps(intervals, lo, hi):
    """The (start, end) stretches of [lo, hi] that no interval covers."""
    gaps, at = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def attribute(gaps, spans, other="between_spans"):
    """{span name: ns}: each gap's time goes to the harness spans that
    cover it; where spans nest, to the innermost (the latest started);
    time under no span goes to ``other``.  ``gaps`` and ``spans`` are
    sorted by start."""
    out, live, nxt = {}, [], 0
    for g0, g1 in gaps:
        while nxt < len(spans) and spans[nxt][1] < g1:
            live.append(spans[nxt])
            nxt += 1
        live = [sp for sp in live if sp[2] > g0]
        cuts = sorted({g0, g1, *(t for _, s, e in live for t in (s, e)
                                 if g0 < t < g1)})
        for a, b in zip(cuts, cuts[1:]):
            cover = [sp for sp in live if sp[1] <= a and sp[2] >= b]
            name = max(cover, key=lambda sp: sp[1])[0] if cover else other
            out[name] = out.get(name, 0) + (b - a)
    return out


def self_times(ops):
    """{name: ns} of each operation's own time: its duration less its
    children's (a ``while`` holds its body's operations)."""
    out, stack = {}, []

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, child = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][3] += end - start

    for name, start, end in ops:
        close(start)
        stack.append([name, start, end, 0])
    close(float("inf"))
    return out


def kernel_events(ops, kernel):
    """The (start, end) of every operation that is the Pallas kernel
    ``kernel``: its name holds the kernel's name.  An operation inside
    another match (a child) is dropped."""
    hits, last_end = [], -1
    for name, start, end in ops:
        if kernel in name and start >= last_end:
            hits.append((start, end))
            last_end = end
    return hits


class Trace:
    """A reduced trace: what the readers in ``perf/metrics`` are given."""

    def __init__(self, raw):
        self.ops = device_ops(raw)
        self.spans = host_spans(raw)
        if not self.spans:
            raise ValueError("the trace holds none of the harness's spans")
        self.lo = self.spans[0][1]
        self.hi = max(e for _, _, e in self.spans)

    @property
    def window_s(self):
        return (self.hi - self.lo) / 1e9

    def chips(self):
        return sorted(self.ops)

    def busy_s(self):
        """Device-busy seconds in the window, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(busy_ns([(s, e) for _, s, e in ops], self.lo, self.hi)
                   for ops in self.ops.values()) / len(self.ops) / 1e9

    def spans_named(self, name):
        return [(s, e) for n, s, e in self.spans if n == name]

    def busy_within(self, lo, hi, chip=None):
        chip = self.chips()[0] if chip is None else chip
        return busy_ns([(s, e) for _, s, e in self.ops[chip]], lo, hi)

    def kernel_seconds(self, kernel, chip=None):
        """(calls, summed device seconds) of a kernel in the window."""
        chip = self.chips()[0] if chip is None else chip
        ev = clip(kernel_events(self.ops[chip], kernel), self.lo, self.hi)
        return len(ev), sum(e - s for s, e in ev) / 1e9

    def breakdown(self, top=10):
        chip = self.chips()[0]
        ops = [o for o in self.ops[chip]
               if o[2] > self.lo and o[1] < self.hi]
        # the layers' copies of one instruction differ only in their
        # number: %fusion.812 and %fusion.813 of one result type are
        # added up as "%fusion bf16[8,1024,1024]"
        grouped = {}
        for name, t in self_times(ops).items():
            key = re.sub(r"\.\d+( |$)", r"\1", name)
            grouped[key] = grouped.get(key, 0) + t
        per_op = sorted(grouped.items(), key=lambda kv: -kv[1])
        gaps = idle_gaps([(s, e) for _, s, e in ops], self.lo, self.hi)
        per_gap = sorted(attribute(gaps, self.spans).items(),
                         key=lambda kv: -kv[1])
        return {"device_ops": [[n, t / 1e9] for n, t in per_op[:top]],
                "idle_gaps": [[n, t / 1e9] for n, t in per_gap[:top]]}
