"""PR 46, NOT a chip run: one layer's ``sparse_moe`` gradient at Mellum2's
shapes, compiled for a described v5e as a captured TPU program would be,
with the grouped products by the kernels (default) or by ``ragged_dot``
(``KERNEL=0``): seconds to compile, the kernels' calls and the scope each
carries in its ``op_name``, the temporaries.

    JAX_PLATFORMS=cpu [KERNEL=0] python3 _archive/pr46_sparse_moe_compile.py
"""
import functools
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

sys.path.insert(0, ".")
from paddle_tpu.core import scope  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import sparse_moe  # noqa: E402

KERNEL = os.environ.get("KERNEL", "1") == "1"
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])


def chip(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one)


if KERNEL:      # compiled, not interpreted
    jax.default_backend = lambda: "tpu"
n, h, i, held, router, k = 8192, 2304, 896, 8, 64, 8
fn = functools.partial(sparse_moe, top_k=k, expert_offset=0,
                       scoring="softmax", slots_at_a_time=16384,
                       grouped_kernel=KERNEL)


def grads(x, gate, w1, w3, w2, bias):
    with scope.capture():
        return jax.value_and_grad(
            lambda *a: fn(*a, bias=bias)[0].astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4))(x, gate, w1, w3, w2)


t = time.time()
compiled = jax.jit(grads).lower(
    chip((n, h)), chip((h, router)), chip((held, h, i)), chip((held, h, i)),
    chip((held, i, h)), chip((router,), jnp.float32)).compile()
text = compiled.as_text()
print(f"compiled in {time.time() - t:.1f} s; ragged-dot:",
      "ragged-dot" in text, "; temporaries",
      compiled.memory_analysis().temp_size_in_bytes)
names = {}
for line in text.split("\n"):
    if "tpu_custom_call" in line and "grouped_matmul" in line:
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        names[name] = names.get(name, 0) + 1
for name, count in sorted(names.items()):
    print(count, name)
