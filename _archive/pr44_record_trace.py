"""Scratch (not committed): after a --trace 1 run of laguna-xs.2.pretrain_8k,
cut its trace to a recorded one small enough to keep beside the tests:
the operations under the ``out_gate`` scope and the flash kernels of a
stretch of about two steps, with the host spans inside it.  Writes
chiprun_out/recorded_laguna_gate_trace.json.

    python3 _archive/pr44_record_trace.py [steps]
"""
import json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from perf import phase_reduce as pr, trace_reduce as tr

CELL = "laguna-xs.2.pretrain_8k"
HARNESS = ("input_feed", "train_step", "loss_readback")
raw = pr.load(tr.find_xplane(os.path.join(ROOT, ".perf_trace", CELL)), spans=pr.SPANS + HARNESS)
host = [e for p in raw["planes"] if p["name"] == tr.HOST_PLANE for ln in p["lines"] for e in ln["events"]]
calls = sorted(e for e in host if e[0] == pr.CALL)
steps = sorted(e for e in host if e[0] == "train_step")
dev = [ln for p in raw["planes"] if p["name"] != tr.HOST_PLANE for ln in p["lines"] if "op_names" in ln][0]
# device steps by their first window kernel: three flash_window_fwd a step
firsts = sorted(s for (name, s, d) in dev["events"] if "flash_window_fwd" in name)
per_step = 3
starts = firsts[::per_step]
k = 3                       # a steady step, past the window's first
lo, hi = starts[k] - 1000, starts[k + 2] - 1000
keep = [i for i, ((name, s, d), op) in enumerate(zip(dev["events"], dev["op_names"]))
        if s >= lo and s + d <= hi and ("/out_gate/" in op or "flash_" in name)]
events = [dev["events"][i] for i in keep]
names = [dev["op_names"][i] for i in keep]
inside = [dev.get("inside", [""] * len(dev["events"]))[i] for i in keep]
# two host calls: the ones issued nearest before the stretch (the host runs ahead of the device)
issued = [c for c in calls if c[1] < lo][-2:]
shift = lo + 2000 - issued[0][1]
spans = [["train_step", lo, hi - lo]] + [[c[0], c[1] + shift + j * 1000, min(c[2], 1000)] for j, c in enumerate(issued)]
out_gate = sum(d for (n, s, d), op in zip(events, names) if "/out_gate/" in op)
kernels = sum(d for (n, s, d), op in zip(events, names) if "/out_gate/" not in op)
rec = {
    "source": f"{CELL}, --trace 1, TPU v5 lite, PR 44 (perf/phase_reduce.load, then _archive/pr44_record_trace.py): two consecutive steady device steps of the traced window (from the fourth step's first flash_window_fwd to the sixth's), of which ONLY the operations whose op_name lies under the out_gate scope and the four flash kernels are kept, every one of them, with their op_names from the trace's HLO modules; the host spans are one train_step over the stretch and two to_static.call, moved into it (the host issues a step long before the device runs it)",
    "window": [lo, hi],
    "raw": {"planes": [
        {"name": [p["name"] for p in raw["planes"] if p["name"] != tr.HOST_PLANE][0],
         "lines": [{"name": dev["name"], "events": events, "op_names": names, "inside": inside}]},
        {"name": tr.HOST_PLANE, "lines": [{"name": "python3", "events": spans}]}]},
    "expect": {"calls": 2, "out_gate_ns": out_gate, "kernel_ns": kernels,
               "out_gate_events": sum("/out_gate/" in op for op in names), "kernel_events": sum("/out_gate/" not in op for op in names)},
}
os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
path = os.path.join(ROOT, "chiprun_out", "recorded_laguna_gate_trace.json")
json.dump(rec, open(path, "w"))
print("recorded", len(events), "events", os.path.getsize(path), "bytes", rec["expect"])
print(sorted({op.split("/out_gate/")[0].rsplit("/", 3)[-1] + "/out_gate/" + op.split("/out_gate/")[1] for op in names if "/out_gate/" in op})[:40])
