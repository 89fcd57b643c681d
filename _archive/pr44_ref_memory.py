"""PR 44: memory_analysis() of the reference's grad_block (perf/reference/common.train_three_steps) for laguna-xs.2 at full size, compiled for a DESCRIBED v5e (not a chip run).  Arguments: layers_kept=0,1 or any key of the configuration, or a module constant of perf/reference/laguna.py (QUERY_BLOCK=128)."""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from perf.reference import laguna as R, common as C
cfg = json.load(open(os.path.join(ROOT, "perf/configs/laguna-xs.2.json")))
for kv in sys.argv[1:]:
    k, v = kv.split("=")
    if k == "layers_kept":
        cfg[k] = [int(x) for x in v.split(",")]
    elif k in cfg:
        cfg[k] = int(v)
    else:
        setattr(R, k, int(v))
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
jax.config.update("jax_enable_compilation_cache", False)
table = R.table(cfg)
params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one) for k, (s, _, _) in table.items()}
spec = {"rows": 1, "seq_len": 8192}
loss_rows = R.train_loss_rows(cfg, spec, C.Matmul("highest"))
def grad_block(params, acc, rows):
    (_, parts), g = jax.value_and_grad(lambda p: loss_rows(p, *rows), has_aux=True)(params)
    return jax.tree.map(jnp.add, acc, g), parts
ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32, sharding=one)
t = time.time()
with jax.default_matmul_precision("highest"):
    c = jax.jit(grad_block, donate_argnums=1).lower(params, params, (ids, ids)).compile()
m = c.memory_analysis()
print("params GB", 4 * R.parameters(cfg) / 1e9)
print(sys.argv[1:], "compile s", round(time.time() - t), "args GB", m.argument_size_in_bytes / 1e9, "temp GB", m.temp_size_in_bytes / 1e9,
      "out", m.output_size_in_bytes / 1e9, "alias", m.alias_size_in_bytes / 1e9, "code GB", m.generated_code_size_in_bytes / 1e9)

