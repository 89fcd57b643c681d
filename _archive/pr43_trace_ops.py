"""After a traced run in checkout ROOT: the device operations of the
traced stretch grouped by kind and result type, with their count and
self seconds, and the count of `to_static.call`-sized steps, as JSON.

    python _archive/pr43_trace_ops.py ROOT CELL OUT.json
"""
import json
import os
import re
import sys

root, cell, out = sys.argv[1:4]
sys.path.insert(0, os.path.abspath(root))
from perf import trace_reduce as tr  # noqa: E402

SPANS = ("train_step", "input_feed", "loss_readback")
path = tr.find_xplane(os.path.join(root, ".perf_trace", cell))
trace = tr.Trace(tr.load_xplane(path, SPANS))
chip = trace.chips()[0]
ops = [o for o in trace.ops[chip] if o[2] > trace.lo and o[1] < trace.hi]
counts, times = {}, {}
for name, _, _ in ops:
    key = re.sub(r"\.\d+( |$)", r"\1", name)
    counts[key] = counts.get(key, 0) + 1
for name, t in tr.self_times(ops).items():
    key = re.sub(r"\.\d+( |$)", r"\1", name)
    times[key] = times.get(key, 0) + t
steps = len(trace.spans_named("train_step"))
rows = sorted(([k, counts[k], times.get(k, 0) / 1e9] for k in counts),
              key=lambda r: -r[2])
json.dump({"steps": steps, "busy_s": trace.busy_s(),
           "window_s": trace.window_s, "ops": rows[:120]},
          open(out, "w"))
for k, n, t in rows[:45]:
    print(f"{t / steps * 1e3:9.3f} ms a step  {n / steps:7.1f} a step  {k}")
