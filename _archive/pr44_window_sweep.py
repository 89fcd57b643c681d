"""Scratch (not committed): the window pair's block sizes on the chip at
b1 h64/8 s8192 d128 W512, bfloat16: ms a call by the scan-slope timing the
autotuner uses (two scan lengths in one jit each; dispatch cancels)."""
import json, os, sys, time
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
os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
out = open(os.path.join(ROOT, "chiprun_out", "pr44_window_sweep.jsonl"), "a")


def fwd_runner(cand, window):
    def make(reps):
        def chained(a, bb, cc):
            def body(c, i):
                o = fa._flash_bhsd(a + i.astype(a.dtype) * 1e-6, bb, cc, None, None, scale, True, False,
                                   cand, None, window)
                return c + o, None
            return jax.lax.scan(body, jnp.zeros_like(a), jnp.arange(reps))[0]
        return jax.jit(chained)
    return make


def bwd_runner(fwd_cand, cand, window):
    def make(reps):
        grad = jax.grad(lambda a, bb, cc: fa._flash_bhsd(
            a, bb, cc, None, None, scale, True, False, fwd_cand, cand, window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))

        def chained(a, bb, cc):
            def body(c, i):
                dq, dk, dv = grad(a + i.astype(a.dtype) * 1e-6, bb, cc)
                return c + dq + (dk.sum() + dv.sum()).astype(a.dtype), None
            return jax.lax.scan(body, jnp.zeros_like(a), jnp.arange(reps))[0]
        return jax.jit(chained)
    return make


def ms(make):
    try:
        return 1e3 * fa._scan_slope(make, (q, k, v), r1=4, r2=20)
    except Exception as e:      # a candidate the compiler refuses
        return f"{type(e).__name__}: {str(e)[:200]}"


def say(**kw):
    line = json.dumps(kw)
    print(line, flush=True)
    out.write(line + "\n"); out.flush()


say(device=jax.devices()[0].device_kind, shape=f"b{B}h{H}/{KV}s{S}d{D}w{W}")
FWD = [(512, 512), (256, 512), (512, 256), (256, 256), (128, 512), (128, 256), (256, 128), (128, 128), (1024, 512), (256, 1024)]
fwd_ms = {}
for cand in FWD:
    fwd_ms[cand] = ms(fwd_runner(cand, W))
    say(kernel="window_fwd", blocks=cand, ms=fwd_ms[cand])
best_fwd = min((c for c in FWD if isinstance(fwd_ms[c], float)), key=fwd_ms.get)
say(best_fwd=best_fwd)
BWD = [(512, 512), (256, 512), (512, 256), (256, 256), (128, 512), (128, 256), (256, 128), (1024, 512), (1024, 256)]
for cand in BWD:
    both = ms(bwd_runner(best_fwd, cand, W))
    say(kernel="window_fwd+bwd", fwd_blocks=best_fwd, blocks=cand, ms=both,
        bwd_ms=both - fwd_ms[best_fwd] if isinstance(both, float) else None)
# the full layers' pair (48 query heads, a group of 6), the defaults
q = q[:, :48]
plain_f = ms(fwd_runner(None, None))
plain_b = ms(bwd_runner(None, None, None))
say(kernel="causal_fwd_h48", ms=plain_f)
say(kernel="causal_fwd+bwd_h48", ms=plain_b, bwd_ms=plain_b - plain_f)
