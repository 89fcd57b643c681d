"""PR 45: where a cell's set-up goes, step by step.  Builds the cell's
training program as ``perf/run.py`` does (the checkout's own compile
cache on), and times the build and each of the first four calls of the
step (the first runs eagerly, the second captures and compiles or reads
the program back, the rest replay it), with the programs the backend
produced in each and their seconds (JAX's monitoring event).  On the
chip at the cell's size; ``PR45_REHEARSE=1``: one row of 128 here.

    python3 _archive/pr45_setup_phases.py ROOT CONFIG
"""
import json
import os
import sys
import time

T0 = time.time()
ROOT = os.path.abspath(sys.argv[1])
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.monitoring  # noqa: E402
import numpy as np  # noqa: E402

from paddle_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402
from perf import loader  # noqa: E402

enable_compile_cache()
seen = {"programs": 0, "seconds": 0.0}


def on(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        seen["programs"] += 1
        seen["seconds"] += secs


jax.monitoring.register_event_duration_secs_listener(on)
rehearsal = bool(os.environ.get("PR45_REHEARSE"))
assert rehearsal or jax.devices()[0].platform == "tpu"
cfg = loader.data("configs", sys.argv[2])
adapter = loader.module("models", cfg["family"])
seq = 128 if rehearsal else 8192


def phase(name, fn):
    before, t = dict(seen), time.time()
    out = fn()
    print(json.dumps({"root": os.path.basename(ROOT), "phase": name,
                      "s": round(time.time() - t, 2),
                      "programs": seen["programs"] - before["programs"],
                      "compile_s": round(seen["seconds"]
                                         - before["seconds"], 2)}),
          flush=True)
    return out


print(json.dumps({"phase": "imports", "s": round(time.time() - T0, 2)}))
program = phase("build", lambda: adapter.build_train(
    cfg, {"rows": 1, "seq_len": seq}))
ids = np.zeros((1, seq), np.int32)
for i in range(4):
    phase(f"step {i + 1}", lambda: float(program.step(program.feed((ids, ids)))))
print(json.dumps({"phase": "all", "s": round(time.time() - T0, 2)}))
