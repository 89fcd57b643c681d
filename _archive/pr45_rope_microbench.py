"""PR 45, on the chip: the rotation alone, at the cells' shapes.

    python3 _archive/pr45_rope_microbench.py [--sweep]

One row of 8192 positions, bfloat16, q at 64 / 48 / 32 heads and k at 8 /
4, tables 128 and 64 wide.  For each: the kernel's result and gradient
against the jnp form's ON THE CHIP (largest difference, in units of the
last place at the operand's size), then the seconds of (a) the jnp form,
rotation + the attention wrapper's swapaxes, forward and backward as XLA
compiles them, and (b) ``rope.half_turn`` forward and backward, each as
the median of 5 batches of 20 calls, with the share of HBM's 819 GB/s
the kernel's bytes (operand read once, result written once, two tables)
come to.  ``--sweep``: (b) over ``_ROWS`` at one block, then over
``BLOCK_S`` x ``BLOCK_H`` at the best of those, at 64 heads and at 8,
tables 128 wide and 64.  One JSON line each.
"""
import itertools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models import lfm2  # noqa: E402
from paddle_tpu.models.llama import rope_angles  # noqa: E402
from paddle_tpu.ops.pallas import rope  # noqa: E402

import os  # noqa: E402
REHEARSAL = bool(os.environ.get("PR45_REHEARSE"))   # tiny, on the CPU
SEQ, DIM = (64 if REHEARSAL else 8192), 128
assert REHEARSAL or jax.devices()[0].platform == "tpu", jax.devices()


def seconds(fn, *args):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(5):
        t = time.perf_counter()
        for _ in range(20):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t) / 20)
    return statistics.median(took)


def jnp_form(x, cos, sin):
    """The parent's ``_rotate`` on one operand, whatever this tree's
    ``_rotate`` does on a TPU."""
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    r = cos.shape[-1]
    whole = r == x.shape[-1]
    x32 = (x if whole else x[..., :r]).astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    out = (x32 * c + jnp.concatenate([-x2, x1], axis=-1) * s).astype(x.dtype)
    return out if whole else jnp.concatenate([out, x[..., r:]], axis=-1)


def pair(how, heads, cos, sin):
    """(forward, backward) jitted: [1, S, H * 128] -> [1, H, S, 128], as
    a projection writes and the attention kernels read."""
    def fwd(x):
        x = x.reshape(1, SEQ, heads, DIM)
        y = (rope.half_turn(x, cos, sin) if how == "kernel"
             else jnp_form(x, cos, sin))
        return jnp.swapaxes(y, 1, 2)
    return jax.jit(fwd), jax.jit(lambda x, g: jax.vjp(fwd, x)[1](g)[0])


def units(got, want, operand):
    got, want, operand = (np.asarray(a, np.float64)
                          for a in (got, want, operand))
    size = np.maximum(np.abs(want), np.abs(operand).max(-1, keepdims=True))
    return float((np.abs(got - want)
                  / 2.0 ** (np.floor(np.log2(size)) - 7)).max())


def case(heads, r, check=True, jnp_too=True):
    theta, scale = (10000.0, 1.0) if r == DIM else (500000.0, 1.4159)
    cos, sin = rope_angles(np.arange(SEQ), r, theta, scale=scale)
    key = jax.random.key(heads + r)
    x = jax.random.normal(key, (1, SEQ, heads * DIM), jnp.bfloat16)
    g = jax.random.normal(jax.random.fold_in(key, 1),
                          (1, heads, SEQ, DIM), jnp.bfloat16)
    bytes_ = 2 * x.size * 2 + 2 * SEQ * DIM * 4
    line = {"heads": heads, "r": r, "bytes": bytes_,
            "floor_ms": bytes_ / 819e9 * 1e3}
    kf, kb = pair("kernel", heads, cos, sin)
    line["kernel_fwd_ms"] = seconds(kf, x) * 1e3
    line["kernel_bwd_ms"] = seconds(kb, x, g) * 1e3
    line["kernel_share_of_hbm"] = 2 * line["floor_ms"] / (
        line["kernel_fwd_ms"] + line["kernel_bwd_ms"])
    if jnp_too or check:
        jf, jb = pair("jnp", heads, cos, sin)
    if jnp_too:
        line["jnp_fwd_ms"] = seconds(jf, x) * 1e3
        line["jnp_bwd_ms"] = seconds(jb, x, g) * 1e3
    if check:
        xs = x.reshape(1, SEQ, heads, DIM).swapaxes(1, 2)
        line["fwd_units"] = units(kf(x), jf(x), xs)
        line["bwd_units"] = units(
            kb(x, g).reshape(1, SEQ, heads, DIM),
            jb(x, g).reshape(1, SEQ, heads, DIM), g.swapaxes(1, 2))
    return line


def main():
    if "--sweep" in sys.argv:
        def swept(bs, hb, rows, heads, r):
            rope.BLOCK_S, rope.BLOCK_H, rope._ROWS = bs, hb, rows
            line = {"BLOCK_S": bs, "BLOCK_H": hb, "ROWS": rows}
            try:
                line.update(case(heads, r, check=False, jnp_too=False))
            except Exception as e:     # more VMEM than a kernel may use
                line.update(heads=heads, r=r, refused=" ".join(
                    str(e).split())[:200])
            print(json.dumps(line), flush=True)
            return line.get("kernel_fwd_ms", 1e9) + line.get(
                "kernel_bwd_ms", 1e9)

        # the rows a pass of the body takes, at one block; then the
        # block, at the best of those
        rows = min((16, 32, 64, 128),
                   key=lambda rows: swept(512, 8, rows, 64, 128))
        for bs, hb in itertools.product((256, 512, 1024, 2048), (4, 8, 16)):
            if REHEARSAL and (bs, hb) != (512, 8):
                continue
            for heads, r in ((64, 128), (64, 64), (8, 128)):
                swept(bs, hb, rows, heads, r)
        return
    for heads, r in ((64, 128), (48, 64), (32, 128), (8, 128), (8, 64),
                     (4, 128)):
        print(json.dumps(case(heads, r)), flush=True)


main()
