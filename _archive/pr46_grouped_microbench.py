"""PR 46, on the chip: the grouped products alone, at the five sparse
cells' shapes and routed loads.

    python3 _archive/pr46_grouped_microbench.py [--sweep [--only-sweep]] [cell ...]

For each cell (rows a chunk, held experts, hidden width H, expert width
I, slots routed to a layer) and each orientation (``up``: [M, H] x [G, H,
I], twice a pass; ``down``: [M, I] x [G, I, H]): the three products
(result, rows' gradient, weights' gradient) by ``jax.lax.ragged_dot`` and
its ``jax.vjp``, and by ``ops/pallas/grouped_matmul.py`` at the committed
tile rule: ms a call (the median of 5 batches of 20 calls) and the share
of the MXU's 197 TFLOP/s the ROUTED rows' FLOPs come to, and the largest
difference of the two results on the chip.  ``--sweep``: the kernels
again over row tiles at the rule's widths and over width tiles at the
rule's row tile (``--only-sweep``: without the comparison).  One JSON
line each, also appended to
``chiprun_out/pr46_microbench.jsonl``.
"""
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from paddle_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402

REHEARSAL = bool(os.environ.get("PR46_REHEARSE"))   # tiny, on the CPU
assert REHEARSAL or jax.devices()[0].platform == "tpu", jax.devices()
PEAK = 197e12
# rows a chunk, held, H, I, slots routed to a layer in a step (PERF.md
# section 5: ``routed_here_share.train`` x the router's slots; LFM2's
# first chunk is full)
CELLS = {"mellum2-12b-a2.5b": (16384, 8, 2304, 896, 8257),
         "moonlight-16b-a3b": (8192, 8, 2048, 1408, 6100),
         "laguna-xs.2": (16384, 32, 2048, 512, 8200),
         "lfm2-24b-a2b": (8192, 8, 2048, 1536, 8192),
         "kimi-linear-48b-a3b": (8192, 8, 2304, 1024, 2100)}
if REHEARSAL:
    CELLS = {"toy": (512, 4, 256, 384, 300)}
OUT = "chiprun_out/pr46_microbench.jsonl"


def seconds(fn, *args):
    jax.block_until_ready(fn(*args))
    took = []
    for _ in range(2 if REHEARSAL else 5):
        t = time.perf_counter()
        for _ in range(2 if REHEARSAL else 20):
            out = fn(*args)
        jax.block_until_ready(out)
        took.append((time.perf_counter() - t) / (2 if REHEARSAL else 20))
    return statistics.median(took)


def say(**row):
    line = json.dumps(row)
    print(line, flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def sizes_of(g, routed, seed):
    """An uneven split of ``routed`` rows over ``g`` groups."""
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.full(g, 8.0))
    return jnp.asarray(rng.multinomial(routed, p), jnp.int32)


def ragged(kind):
    def both(x, w, s, dy):
        y, back = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, s), x, w)
        return {"fwd": lambda: y, "dx": lambda: back(dy)[0],
                "dw": lambda: back(dy)[1]}[kind]()
    return jax.jit(both)


def kernel(kind, tiling=None):
    def one(x, w, s, dy):
        tm = tiling[0] if tiling else gm._row_tile(x.shape[0])
        plan = gm._plan(s, m=x.shape[0], tm=tm, tail=kind != "dw")
        if kind == "fwd":
            return gm._rows_call(x, w, plan, turned=False,
                                 interpret=REHEARSAL, tiling=tiling)
        if kind == "dx":
            return gm._rows_call(dy, w, plan, turned=True,
                                 interpret=REHEARSAL, tiling=tiling)
        return gm._weights_call(x, dy, plan, interpret=REHEARSAL,
                                tiling=tiling)
    return jax.jit(one)


def candidates(kind, m, g, k, n):
    """Row tiles at the rule's widths, width tiles at the rule's rows."""
    tm, tk, tn = gm.tiles(kind, m, g, k, n)
    if kind == "dx":
        k, n = n, k
    out = [(t, tk, tn) for t in (128, 256, 512, 1024)
           if t != tm and m % t == 0]
    out += [(tm, a, b) for a in gm._divisors(k)[:3]
            for b in gm._divisors(n)[:3] if (a, b) != (tk, tn)]
    return [t for t in out if gm.vmem_bytes(kind, *t) <= 56 << 20]


def main(argv):
    sweep, only = "--sweep" in argv, "--only-sweep" in argv
    cells = [a for a in argv if not a.startswith("--")] or list(CELLS)
    for cell in cells:
        m, g, h, i, routed = CELLS[cell]
        s = sizes_of(g, routed, 46)
        for name, (k, n) in (("up", (h, i)), ("down", (i, h))):
            ka, kb, kc = jax.random.split(jax.random.PRNGKey(46), 3)
            x = jax.random.normal(ka, (m, k), jnp.bfloat16)
            w = jax.random.normal(kb, (g, k, n), jnp.bfloat16) * k ** -0.5
            dy = jax.random.normal(kc, (m, n), jnp.bfloat16)
            flops = 2.0 * routed * k * n
            for kind in ("fwd", "dx", "dw"):
                rule = gm.tiles(kind, m, g, k, n)
                ours = kernel(kind)
                if only:
                    took = seconds(ours, x, w, s, dy)
                    say(cell=cell, product=name, kind=kind, sweep=rule,
                        rule=True, kernel_ms=took * 1e3,
                        kernel_peak_share=flops / took / PEAK)
                else:
                    compare(cell, name, kind, (m, g, k, n), routed, rule,
                            ours, x, w, s, dy)
                if not sweep:
                    continue
                for t in candidates(kind, m, g, k, n):
                    try:
                        took = seconds(kernel(kind, tuple(t)), x, w, s, dy)
                        say(cell=cell, product=name, kind=kind, sweep=t,
                            kernel_ms=took * 1e3,
                            kernel_peak_share=flops / took / PEAK)
                    except Exception as e:   # what Mosaic or VMEM refuses
                        say(cell=cell, product=name, kind=kind, sweep=t,
                            refused=str(e)[:200])


def compare(cell, name, kind, shape, routed, rule, ours, x, w, s, dy):
    """The kernel at the rule's tiles beside ``ragged_dot``: seconds,
    share of the peak on the routed rows, largest difference."""
    m, g, k, n = shape
    flops = 2.0 * routed * k * n
    ref = ragged(kind)
    want, got = ref(x, w, s, dy), ours(x, w, s, dy)
    if kind != "dw":
        want = jnp.where((jnp.arange(m) < routed)[:, None], want, 0)
    diff = jnp.max(jnp.abs(got.astype(jnp.float32)
                           - want.astype(jnp.float32)))
    t_ref, t_ours = seconds(ref, x, w, s, dy), seconds(ours, x, w, s, dy)
    say(cell=cell, product=name, kind=kind, m=m, g=g, k=k, n=n,
        routed=routed, tiles=rule, ragged_ms=t_ref * 1e3,
        kernel_ms=t_ours * 1e3, ragged_peak_share=flops / t_ref / PEAK,
        kernel_peak_share=flops / t_ours / PEAK, max_abs_diff=float(diff),
        largest=float(jnp.max(jnp.abs(want.astype(jnp.float32)))))

if __name__ == "__main__":
    main(sys.argv[1:])
