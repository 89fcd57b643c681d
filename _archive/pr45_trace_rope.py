"""After a traced run in checkout ROOT: the traced stretch's device time
by phase, by scope, and the scope ``rope`` by layer type and phase, ms a
step (``perf/phase_reduce.PhaseTable``, the readers' own reduction).

    python3 _archive/pr45_trace_rope.py ROOT CELL [OUT.json]
"""
import collections
import json
import os
import sys

root, cell = os.path.abspath(sys.argv[1]), sys.argv[2]
sys.path.insert(0, root)
from perf import phase_reduce as pr  # noqa: E402
from perf import trace_reduce as tr  # noqa: E402

HARNESS = ("train_step", "input_feed", "loss_readback")
path = tr.find_xplane(os.path.join(root, ".perf_trace", cell))
trace = tr.Trace(tr.load_xplane(path, HARNESS))
table = pr.PhaseTable(pr.load(path), trace.lo, trace.hi)
steps = table.calls


def ms(ns):
    return round(ns / steps / 1e6, 3)


by_phase = collections.Counter()
by_scope = collections.Counter()
rope = collections.Counter()
rope_rows = collections.Counter()
rows = collections.Counter()
for c, ns in table.cells.items():
    parts = c.path.split("/")
    by_phase[c.phase] += ns
    rows[c.row] += ns
    for scope in ("window_attention", "full_attention", "rope", "qkv",
                  "o_proj", "out_gate", "router", "dispatch", "combine",
                  "expert_mlp", "shared_expert"):
        if scope in parts:
            by_scope[scope] += ns
    if "rope" in parts:
        kind = next((p for p in parts if p.endswith("_attention")), "?")
        rope[f"{kind} {c.phase}"] += ns
        rope_rows[f"{kind} {c.phase} {c.row}"] += ns
out = {
    "steps": steps, "busy_ms_a_step": ms(table.busy_ns),
    "by_phase": {k: ms(v) for k, v in by_phase.most_common()},
    "by_scope": {k: ms(v) for k, v in by_scope.most_common()},
    "rope": {k: ms(v) for k, v in sorted(rope.items())},
    "rope_all_phases": ms(sum(rope.values())),
    "rope_rows": {k: ms(v) for k, v in rope_rows.most_common(24)},
    "rows": {k: ms(v) for k, v in rows.most_common(40)},
    "reduce_precision": ms(sum(v for k, v in rows.items()
                               if "reduce-precision" in k
                               or "reduce_precision" in k)),
}
print(json.dumps(out, indent=1))
if len(sys.argv) > 3:
    json.dump(out, open(sys.argv[3], "w"))
