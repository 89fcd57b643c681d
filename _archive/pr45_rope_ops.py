"""PR 45's sizing script: what the rotation of q and k compiles to, read
from a compile for a DESCRIBED v5e (no chip, no chip time; after
``_archive/pr43_kda_glue_ops.py``, whose ``entry_ops`` it borrows).

    JAX_PLATFORMS=cpu python _archive/pr45_rope_ops.py [ROOT ...]

For every checkout ``ROOT`` (default: this one; give ``_parent`` and
``.`` to compare two), in a child process each:

* **operator**: ``lfm2._rotate`` forward + backward on q [1, 8192, 64 *
  128] and k [1, 8192, 8 * 128] bfloat16 as the projections write them
  (four dimensions by a reshape), tables 128 and 64 wide, followed by
  the attention wrapper's ``swapaxes``;
* **layer**: ``MellumAttention`` (window 512, 64 + 8 heads of 128,
  hidden 2048, a gate: Laguna's window layer) at 1 x 8192 under
  ``jax.checkpoint`` with the cells' policy
  (``dots_and_kernels_saveable``) and AMP O2, as a pure function of its
  input and parameters, the kernels steered on: what
  ``jax.ad_checkpoint.print_saved_residuals`` keeps, and the compiled
  forward + recompute + backward.

Prints the entry computation's operations by kind and result shape with
the compiler's estimated cycles (NOT chip time), the count of each
kernel's calls, the results shaped as the float32 halves ISSUE 45 names,
and ``memory_analysis().temp_size_in_bytes``.
"""
import importlib.util
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ, HEADS, KV, DIM, HIDDEN, WINDOW = 8192, 64, 8, 128, 2048, 512
HALVES = ("f32[1,8192,64,64]", "f32[1,8192,64,128]")
KERNELS = ("rope_half_turn_fwd", "rope_half_turn_bwd", "flash_window_fwd",
           "flash_window_bwd")


def report(what, compiled):
    spec = importlib.util.spec_from_file_location(
        "pr43", os.path.join(HERE, "_archive", "pr43_kda_glue_ops.py"))
    pr43 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(pr43)
    text = compiled.as_text()
    ops = pr43.entry_ops(text)
    total = sum(c for _, c in ops.values())
    print(f"== {what}: {sum(n for n, _ in ops.values())} operations, "
          f"{total / 1e6:.2f} M estimated cycles (the compiler's, not the "
          f"chip's), temp_size_in_bytes "
          f"{compiled.memory_analysis().temp_size_in_bytes:,}")
    for (kind, shape), (n, cycles) in sorted(
            ops.items(), key=lambda kv: -kv[1][1]):
        if cycles >= 20_000 or kind == "custom-call":
            print(f"  {n:3d} x {kind:<34} {shape:<44} {cycles:>10,}")
    print("  kernel calls:", {k: text.count(f'"{k}"') or text.count(k)
                              for k in KERNELS})
    print("  results shaped as the float32 halves:",
          {s: text.count(f"= {s}") for s in HALVES})
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
    from paddle_tpu.core import scope
    from paddle_tpu.distributed.fleet.pipeline import functional_call
    from paddle_tpu.distributed.fleet.recompute import _POLICIES
    from paddle_tpu.models import lfm2
    from paddle_tpu.models.mellum import (MellumAttention, MellumConfig,
                                          RopeTables)
    assert os.path.abspath(lfm2.__file__).startswith(os.path.abspath(root))
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bf16, f32 = jnp.bfloat16, jnp.float32

    def chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    jax.default_backend = lambda: "tpu"     # the kernels, not interpreted
    from paddle_tpu.models.llama import rope_angles
    import numpy as np
    out = os.environ.get("PR45_HLO_DIR")
    tag = os.path.basename(os.path.abspath(root))
    for r in (128, 64):
        cos, sin = rope_angles(np.arange(SEQ), r, 10000.0)

        def grads(q, k, gq, gk):
            # a linear map: its cotangents are arguments, or the
            # backward reads nothing that lives on the chip
            def turned(q, k):
                with scope.capture():       # as a to_static step's replay
                    q, k = lfm2._rotate(
                        paddle.Tensor(q.reshape(1, SEQ, HEADS, DIM)),
                        paddle.Tensor(k.reshape(1, SEQ, KV, DIM)), cos, sin)
                return tuple(jnp.swapaxes(a._data, 1, 2) for a in (q, k))
            out, vjp = jax.vjp(turned, q, k)
            return out, vjp((gq, gk))

        text = report(
            f"the rotation by a table {r} wide + swapaxes, forward + "
            f"backward", jax.jit(grads).lower(
                chip((1, SEQ, HEADS * DIM), bf16),
                chip((1, SEQ, KV * DIM), bf16),
                chip((1, HEADS, SEQ, DIM), bf16),
                chip((1, KV, SEQ, DIM), bf16)).compile())
        if out:
            open(os.path.join(out, f"operator{r}_{tag}.hlo"), "w").write(text)

    cfg = MellumConfig(hidden_size=HIDDEN, num_heads=HEADS, num_kv_heads=KV,
                       head_dim=DIM, sliding_window=WINDOW)
    layer = paddle.amp.decorate(
        MellumAttention(cfg, "sliding_attention", RopeTables(cfg),
                        **({"gate": True} if "gate" in
                           MellumAttention.__init__.__code__.co_varnames
                           else {})),
        level="O2", dtype="bfloat16")
    vals = {n: chip(p._data.shape, p._data.dtype)
            for n, p in layer.named_parameters()}

    def block(x, vals):
        with scope.capture(), paddle.amp.auto_cast(level="O2",
                                                   dtype="bfloat16"):
            return functional_call(layer, vals, x)

    block = jax.checkpoint(
        block, policy=_POLICIES["dots_and_kernels_saveable"])
    x = chip((1, SEQ, HIDDEN), bf16)
    print("== saved residuals of the layer under the policy")
    from jax.ad_checkpoint import print_saved_residuals
    print_saved_residuals(
        lambda x, vals: block(x, vals).astype(f32).sum(), x, vals)
    text = report(
        "MellumAttention (window, 64 + 8 heads, gated), forward + "
        "recompute + backward",
        jax.jit(jax.grad(lambda x, vals: block(x, vals).astype(f32).sum(),
                         argnums=(0, 1))).lower(x, vals).compile())
    if out:
        open(os.path.join(out, f"layer_{tag}.hlo"), "w").write(text)


if __name__ == "__main__":
    if os.environ.get("PR45_CHILD"):
        main(sys.argv[1])
    else:
        for root in sys.argv[1:] or [HERE]:
            print(f"#### {root}", flush=True)
            subprocess.run([sys.executable, __file__, root], check=True,
                           env={**os.environ, "PR45_CHILD": "1",
                                "JAX_PLATFORMS": "cpu"})
