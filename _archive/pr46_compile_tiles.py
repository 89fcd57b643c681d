"""PR 46, NOT a chip run: a described-chip compile (v5e:2x2, the compiler
installed here) of the held experts' three grouped products and their
``jax.vjp`` at each sparse cell's shapes.

    JAX_PLATFORMS=cpu python3 _archive/pr46_compile_tiles.py [ragged|kernel]

``ragged``: ``jax.lax.ragged_dot``; prints, per ``%ragged-dot`` custom
call, its ``ragged_dot_tiling`` attribute (the ``window_bounds`` of the
Mosaic body the compiler made of it): its (rows, K, N) tiles, each the
largest of {512, 256, 128} that divides the dimension.  ``kernel``:
``ops/pallas/grouped_matmul.py``; prints the
tiles the committed rule gives and the VMEM a step holds, and compiles the
three kernels (what Mosaic or VMEM refuses fails here).
"""
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
from paddle_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

# cell: rows a chunk, held experts, hidden width, an expert's width
CELLS = {"lfm2-24b-a2b": (8192, 8, 2048, 1536),
         "kimi-linear-48b-a3b": (8192, 8, 2304, 1024),
         "laguna-xs.2": (16384, 32, 2048, 512),
         "moonlight-16b-a3b": (8192, 8, 2048, 1408),
         "mellum2-12b-a2.5b": (16384, 8, 2304, 896)}


def main(which):
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    for cell, (m, g, h, i) in CELLS.items():
        for name, (k, n) in (("up", (h, i)), ("down", (i, h))):
            if which == "ragged":
                dot = jax.lax.ragged_dot
            else:
                dot = lambda x, w, s: gm.grouped_dot(x, w, s, interpret=False)
                for kind in ("fwd", "dx", "dw"):
                    t = gm.tiles(kind, m, g, k, n)
                    print(cell, name, kind, "tiles", t, "vmem MB",
                          round(gm.vmem_bytes(kind, *t) / 2**20, 1))

            def both(x, w, sizes, dy):
                y, back = jax.vjp(lambda x, w: dot(x, w, sizes), x, w)
                return (y,) + back(dy)

            t0 = time.time()
            text = jax.jit(both).lower(
                arg((m, k)), arg((g, k, n)), arg((g,), jnp.int32),
                arg((m, n))).compile().as_text()
            print(cell, name, f"[{m},{k}]x[{g},{k},{n}]",
                  f"compiled in {time.time() - t0:.1f} s;",
                  "ragged-dot calls", len(re.findall(
                      r"custom-call\([^\n]*ragged-dot", text)),
                  "kernel calls", len(re.findall(
                      r"tpu_custom_call[^\n]*grouped_matmul", text)))
            if which == "ragged":
                # result, rows' gradient, weights' gradient, in the
                # program's order: the result's shape says which
                for shape, tiling in re.findall(
                        r"%ragged-dot-none[\w.]* = (\w+\[[\d,]+\])[^\n]*"
                        r'ragged_dot_tiling="([\d,]+)"', text):
                    print("   ", shape, "(tm, tk, tn) =", tiling)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "kernel")
