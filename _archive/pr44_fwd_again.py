"""Scratch (not committed): the window forward at b1 h64/8 s8192 d128 W512, 512 x 512 against 256 x 512, three readings each, in turn."""
import json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from paddle_tpu.ops.pallas import flash_attention as fa
B, S, H, KV, D, W = 1, 8192, 64, 8, 128, 512
scale = D ** -0.5
k0 = jax.random.split(jax.random.key(0), 3)
q = jax.random.normal(k0[0], (B, H, S, D), jnp.bfloat16)
k = jax.random.normal(k0[1], (B, KV, S, D), jnp.bfloat16)
v = jax.random.normal(k0[2], (B, KV, S, D), jnp.bfloat16)
def fwd_runner(cand):
    made = {}
    def make(reps):
        if reps not in made:
            def chained(a, bb, cc):
                def body(c, i):
                    o = fa._flash_bhsd(a + i.astype(a.dtype) * 1e-6, bb, cc, None, None, scale, True, False, cand, None, W)
                    return c + o, None
                return jax.lax.scan(body, jnp.zeros_like(a), jnp.arange(reps))[0]
            made[reps] = jax.jit(chained)
        return made[reps]
    return make
runners = {c: fwd_runner(c) for c in [(512, 512), (256, 512)]}
for turn in range(3):
    for c, make in runners.items():
        print(json.dumps({"kernel": "window_fwd", "blocks": c, "turn": turn, "ms": 1e3 * fa._scan_slope(make, (q, k, v), r1=4, r2=20)}), flush=True)
