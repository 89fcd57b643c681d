"""PR 43's sizing script: what XLA leaves round the two KDA kernels, read
from a compile for a DESCRIBED v5e (no chip, no chip time).

    JAX_PLATFORMS=cpu python _archive/pr43_kda_glue_ops.py [ROOT ...]

For every checkout ``ROOT`` (default: this one; give ``_parent`` and
``.`` to compare two), in a child process each, because only one
process may load the TPU's library and a module is imported once:

* **operator**: ``kda_chunk`` forward + backward at the cell's shape
  ([1, 8192, 32 * 128] bfloat16 as the projections write it, reshaped
  to four dimensions for the call; float32 decay, chunk 128);
* **layer**: ``KimiDeltaAttention`` at the cell's widths (hidden 2304),
  forward + backward under ``jax.checkpoint`` with the cell's policy
  (``dots_and_kernels_saveable``) and AMP O2, as a pure function of
  the input and the parameters (``functional_call``), traced at
  1 x 8192 with the kernels steered on.

Prints the entry computation's operations outside the kernels by kind
and result shape, with their count and the compiler's estimated cycles
(NOT chip time: it says which operations exist), and
``memory_analysis().temp_size_in_bytes``.  The three shapes ISSUE 43
names (the re-tiling of a 134 MB float32 tensor) are marked ``<--``.
"""
import collections
import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RETILED = ("f32[1024,8,32,128]", "f32[1,8192,4096]", "f32[8192,32,128]")
SEQ, HEADS, DIM, HIDDEN, CHUNK = 8192, 32, 128, 2304, 128


def entry_ops(text):
    """{(kind, result shape): [count, estimated cycles]} of the entry
    computation's instructions."""
    entry = text[text.index("\nENTRY "):]
    ops = collections.defaultdict(lambda: [0, 0])
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (\(.*?\)|\S+) ([\w-]+)\(",
                     line)
        if not m:
            continue
        name, shape, kind = m.groups()
        if kind in ("parameter", "get-tuple-element", "tuple", "bitcast",
                    "constant"):
            continue
        if kind == "fusion":
            kind = re.sub(r"\.\d+$", "", name)      # the fusion's own kind
            if "convolution(" in called(text, line):
                kind += "+dot"
        shape = re.sub(r"\{[^}]*\}", "", shape)
        cycles = re.search(r'estimated_cycles":"(\d+)"', line)
        slot = ops[kind, shape]
        slot[0] += 1
        slot[1] += int(cycles.group(1)) if cycles else 0
    return ops


def called(text, line):
    """The body of the computation a fusion line calls."""
    m = re.search(r"calls=%([\w.-]+)", line)
    if not m:
        return ""
    start = text.find(f"\n%{m.group(1)} ")
    return text[start:text.index("\n}", start)] if start >= 0 else ""


def report(what, compiled):
    text = compiled.as_text()
    ops = entry_ops(text)
    total = sum(c for _, c in ops.values())
    print(f"== {what}: {sum(n for n, _ in ops.values())} operations, "
          f"{total / 1e6:.2f} M estimated cycles "
          f"({total / 1.5e6:.2f} ms at 1.5 GHz, the compiler's, not the "
          f"chip's), temp_size_in_bytes "
          f"{compiled.memory_analysis().temp_size_in_bytes:,}")
    def retiled(kind, shape):
        return kind in ("copy", "reshape", "broadcast") and any(
            s in shape for s in RETILED)

    for (kind, shape), (n, cycles) in sorted(
            ops.items(), key=lambda kv: -kv[1][1]):
        if cycles >= 20_000 or retiled(kind, shape):
            mark = "  <--" if retiled(kind, shape) else ""
            print(f"  {n:3d} x {kind:<34} {shape:<40} {cycles:>10,}{mark}")
    counts = {s: sum(n for (kind, shape), (n, _) in ops.items()
                     if retiled(kind, shape) and s in shape)
              for s in RETILED}
    print("  copies, reshapes and broadcasts to a re-tiled shape:",
          json.dumps(counts))
    return text


def main(root):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, root)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.pipeline import functional_call
    from paddle_tpu.distributed.fleet.recompute import _POLICIES
    from paddle_tpu.models.kimi_linear import (KimiDeltaAttention,
                                               KimiLinearConfig)
    from paddle_tpu.ops.pallas import kda
    assert os.path.abspath(kda.__file__).startswith(os.path.abspath(root))
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bf16, f32 = jnp.bfloat16, jnp.float32

    def chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    # as the layer hands them over: [B, S, H * d] from the projections,
    # four dimensions only by a reshape
    x = chip((1, SEQ, HEADS * DIM), bf16)
    g, beta = chip((1, SEQ, HEADS * DIM), f32), chip((1, SEQ, HEADS), f32)

    def grads(q, k, v, g, beta):
        def loss(q, k, v, g, beta):
            q, k, v, g = (a.reshape(1, SEQ, HEADS, DIM) for a in (q, k, v, g))
            return kda.kda_chunk(q, k, v, g, beta, chunk=CHUNK,
                                 how="pallas").astype(f32).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    out = os.environ.get("PR43_HLO_DIR")
    tag = os.path.basename(os.path.abspath(root))
    text = report("operator, forward + backward",
                  jax.jit(grads).lower(x, x, x, g, beta).compile())
    if out:
        open(os.path.join(out, f"operator_{tag}.hlo"), "w").write(text)

    # the layer under the cell's recompute policy and AMP O2: a pure
    # function of the input and the parameters, traced for the chip
    layer = KimiDeltaAttention(KimiLinearConfig(
        hidden_size=HIDDEN, num_heads=HEADS, kda_head_dim=DIM,
        kda_chunk=CHUNK))
    layer = paddle.amp.decorate(layer, level="O2", dtype="bfloat16")
    vals = {n: chip(p._data.shape, p._data.dtype)
            for n, p in layer.named_parameters()}
    jax.default_backend = lambda: "tpu"     # the kernels, not interpreted

    def block(x, vals):
        with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
            return functional_call(layer, vals, x)

    block = jax.checkpoint(
        block, policy=_POLICIES["dots_and_kernels_saveable"])
    text = report(
        "KimiDeltaAttention, forward + recompute + backward",
        jax.jit(jax.grad(lambda x, vals: block(x, vals).astype(f32).sum(),
                         argnums=(0, 1))).lower(
            chip((1, SEQ, HIDDEN), bf16), vals).compile())
    assert "kda_chunk_fwd" in text and "kda_chunk_bwd" in text
    if out:
        open(os.path.join(out, f"layer_{tag}.hlo"), "w").write(text)


if __name__ == "__main__":
    if os.environ.get("PR43_CHILD"):
        main(sys.argv[1])
    else:
        for root in sys.argv[1:] or [HERE]:
            print(f"#### {root}", flush=True)
            subprocess.run([sys.executable, __file__, root], check=True,
                           env={**os.environ, "PR43_CHILD": "1",
                                "JAX_PLATFORMS": "cpu"})
