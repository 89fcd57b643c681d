"""PR 45: memory_analysis() of the steady train step of a cell's
configuration (laguna-xs.2 or mellum2-12b-a2.5b, 1 x 8192), compiled for
a DESCRIBED v5e (`_archive/pr44_steady_memory.py` with the configuration
an argument and the rotation's kernels counted; not a chip run).

    JAX_PLATFORMS=cpu python _archive/pr45_steady_memory.py ROOT CONFIG

The model is built at full widths on the CPU by the cell's adapter in
checkout ROOT, discovered and compiled once at 1 x 128, and its captured
program lowered again at 1 x 8192 with the kernels steered on."""
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.abspath(sys.argv[1])
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from perf import loader  # noqa: E402

cfg = loader.data("configs", sys.argv[2])
adapter = loader.module("models", cfg["family"])
t = time.time()
program = adapter.build_train(cfg, {"rows": 1, "seq_len": 128})
ids = np.zeros((1, 128), np.int32)
tensors = program.feed((ids, ids))
for i in range(3):      # eager discovery, then the steady program
    float(program.step(tensors))
    print("step", i, round(time.time() - t, 1), flush=True)
exe = list(program.train_step._cache.values())[-1]

jax.default_backend = lambda: "tpu"     # the kernels, not interpreted
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
jax.config.update("jax_enable_compilation_cache", False)
vals = [jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one)] * 2 + [
    jax.ShapeDtypeStruct(s._data.shape, s._data.dtype, sharding=one)
    for s in exe.capt_state]
t = time.time()
lowered = exe.compiled.lower(*vals)
lower_s = time.time() - t      # tracing + lowering: what a warm set-up repeats
t = time.time()
compiled = lowered.compile()
compile_s = time.time() - t
m = compiled.memory_analysis()
text = compiled.as_text()
print(json.dumps({
    "root": ROOT, "lower_s": round(lower_s, 2), "compile_s": round(compile_s, 2),
    "argument_GB": m.argument_size_in_bytes / 1e9,
    "alias_GB": m.alias_size_in_bytes / 1e9,
    "temp_bytes": m.temp_size_in_bytes,
    "peak_GB": (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes) / 1e9,
    "kernels": {k: text.count(k) for k in (
        "flash_window_fwd", "flash_window_bwd", "flash_attention_fwd",
        "flash_attention_bwd", "rope_half_turn_fwd", "rope_half_turn_bwd")},
    "kernel_calls": text.count('custom_call_target="tpu_custom_call"'),
    "float32_halves": {s: text.count(f"= {s}") for s in (
        "f32[1,8192,64,64]", "f32[1,8192,32,64]", "f32[1,8192,48,32]")}}))
if len(sys.argv) > 3:
    open(sys.argv[3], "w").write(text)
