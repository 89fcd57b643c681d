"""PR 44, review round: memory_analysis() of perf/reference/common's leaf_sketches and leaf_norms over laguna-xs.2's gradient, and of its apply step, compiled for a DESCRIBED v5e (not a chip run): what runs beside the reference's 16 bytes a parameter after grad_block.  Argument: num_experts=32."""
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from perf.reference import laguna as R, common as C
cfg = json.load(open(os.path.join(ROOT, "perf/configs/laguna-xs.2.json")))
for kv in sys.argv[1:]:
    k, v = kv.split("=")
    cfg[k] = int(v)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
jax.config.update("jax_enable_compilation_cache", False)
params = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one) for k, (s, _, _) in R.table(cfg).items()}
print("state GB", 16 * R.parameters(cfg) / 1e9, "free GB", 16.909 - 16 * R.parameters(cfg) / 1e9)
for name, fn in (("leaf_sketches", C.leaf_sketches), ("leaf_norms", C.leaf_norms)):
    m = jax.jit(fn).lower(params).compile().memory_analysis()
    print(name, "temp GB", m.temp_size_in_bytes / 1e9, "out GB", m.output_size_in_bytes / 1e9, "code GB", m.generated_code_size_in_bytes / 1e9, flush=True)
