"""PR 43, on the chip: the KDA operator with its gate and its gated norm,
forward + backward under jax.checkpoint(dots_and_kernels_saveable), at the
cell's shape; the parent's kda.py (from _parent/) against this tree's, and
this tree's with the sums and spreads as single-pass products of a
three-part bfloat16 split.  Prints ms a call (median of 20)."""
import importlib.util, json, os, statistics, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from jax import lax
from paddle_tpu.distributed.fleet.recompute import _dots_and_kernels_saveable
from paddle_tpu.ops.pallas import kda as new

spec = importlib.util.spec_from_file_location(
    "paddle_tpu.ops.pallas.kda_parent",
    os.path.join(os.environ.get("PR43_PARENT", os.path.join(ROOT, "_parent")), "paddle_tpu/ops/pallas/kda.py"))
old = importlib.util.module_from_spec(spec)
spec.loader.exec_module(old)

S, H, D = (int(x) for x in os.environ.get("SHD", "8192,32,128").split(","))
F32, BF16 = jnp.float32, jnp.bfloat16


def layer_old(q, k, v, f, b, a_log, dt_bias, gate, w):
    shape = (1, S, H, D)
    g = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
        (f.astype(F32) + dt_bias.astype(F32)).reshape(shape))
    beta = jax.nn.sigmoid(b.astype(F32))
    o = old.kda_chunk(q.reshape(shape), k.reshape(shape), v.reshape(shape),
                      g, beta, chunk=128)
    x = o.astype(F32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-5)
    x = x * w.astype(F32) * jax.nn.sigmoid(gate.astype(F32).reshape(o.shape))
    return x.astype(o.dtype).reshape(1, S, -1)


def layer_new(q, k, v, f, b, a_log, dt_bias, gate, w):
    shape = (1, S, H, D)
    g = jnp.repeat(-jnp.exp(a_log.astype(F32)), D) * jax.nn.softplus(
        f.astype(F32) + dt_bias.astype(F32))
    beta = jax.nn.sigmoid(b.astype(F32))
    o = new.kda_chunk(q.reshape(shape), k.reshape(shape), v.reshape(shape),
                      g.reshape(shape), beta, chunk=128).reshape(q.shape)
    return new.gated_head_norm(o, w, gate, H, 1e-5)


def parts(x):
    hi = lax.reduce_precision(x, 8, 7)
    mid = lax.reduce_precision(x - hi, 8, 7)
    return [p.astype(BF16) for p in (hi, mid, x - hi - mid)]


def sum3(x, heads):
    e = new._head_of(x.shape[-1], heads).astype(BF16)
    return sum(lax.dot_general(p, e, (((2,), (0,)), ((), ())),
                               preferred_element_type=F32) for p in parts(x))


def spread3(r, width):
    e = new._head_of(width, r.shape[-1]).astype(BF16)
    return lax.dot_general(
        jnp.concatenate(parts(r), -1), jnp.concatenate([e] * 3, 1),
        (((2,), (1,)), ((), ())), preferred_element_type=F32)


def bench(name, layer):
    keys = jax.random.split(jax.random.key(0), 8)
    big = lambda i: (jax.random.normal(keys[i], (1, S, H * D), F32)).astype(BF16)
    args = (big(0), big(1), big(2), big(3),
            jax.random.normal(keys[4], (1, S, H), F32).astype(BF16),
            0.5 * jax.random.normal(keys[5], (H,), F32),
            2.0 * jax.random.normal(keys[6], (H * D,), F32), big(7),
            jnp.ones((D,), F32))
    ck = jax.checkpoint(layer, policy=_dots_and_kernels_saveable)
    fn = jax.jit(jax.grad(lambda *a: ck(*a).astype(F32).sum(),
                          argnums=tuple(range(9))))
    t = time.time()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.time() - t
    times = []
    for _ in range(20):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t) * 1e3)
    print(json.dumps({"variant": name, "ms": round(statistics.median(times), 3),
                      "min_ms": round(min(times), 3),
                      "compile_s": round(compile_s, 1),
                      "device": jax.devices()[0].device_kind}), flush=True)
    return out


a = bench("parent", layer_old)
b = bench("change_highest", layer_new)
hs, sp = new.head_sum, new.head_spread
new.head_sum, new.head_spread = sum3, spread3
c = bench("change_three_parts", layer_new)
new.head_sum, new.head_spread = hs, sp
for name, x, y in zip("q k v f b a_log dt_bias gate w".split(), a, b):
    x, y = x.astype(F32), y.astype(F32)
    print(name, "parent vs highest", float(jnp.abs(x - y).max() / jnp.abs(x).max()))
for name, x, y in zip("q k v f b a_log dt_bias gate w".split(), b, c):
    x, y = x.astype(F32), y.astype(F32)
    print(name, "highest vs three parts", float(jnp.abs(x - y).max() / jnp.abs(x).max()))
