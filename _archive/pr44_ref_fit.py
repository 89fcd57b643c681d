"""PR 44, review round: what holds the chip's memory when the reference's
grad_block is loaded at the FIRST size (32 held experts, 692M parameters)?
Walks perf/reference/common.train_three_steps' own stages on the chip and
prints the device's memory_stats() after each, the compiled grad_block's
memory_analysis(), and whether it loads and runs: as train_three_steps
holds the state (params, m, v, the gradient sum), then with the jit caches
cleared, then at smaller query blocks, then with the two moments freed
(what a `benchmark` PR could make train_three_steps do).

    python3 _archive/pr44_ref_fit.py [num_experts=32] [seed]
"""
import gc, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import jax, jax.numpy as jnp
from perf.reference import laguna as R, common as C

held = int(sys.argv[1]) if len(sys.argv) > 1 else 32
seed = int(sys.argv[2]) if len(sys.argv) > 2 else 4400000991
cfg = json.load(open(os.path.join(ROOT, os.environ.get("PR44_CFG", "perf/configs/laguna-xs.2.json"))))
cfg["num_experts"] = held
SEQ = int(os.environ.get("PR44_SEQ", 8192))
dev = jax.devices()[0]
KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit", "largest_free_block_bytes", "bytes_reserved",
        "num_allocs", "largest_alloc_size")


def stats(what):
    s = dev.memory_stats() or {}
    row = {"at": what, **{k: round(s[k] / 1e9, 3) if "bytes" in k or "size" in k else s[k] for k in KEYS if k in s}}
    if "bytes_limit" in s:
        row["free_GB"] = round((s["bytes_limit"] - s["bytes_in_use"]) / 1e9, 3)
    print(json.dumps(row), flush=True)
    return s


print(json.dumps({"held": held, "parameters": R.parameters(cfg), "float32_state_GB": 16 * R.parameters(cfg) / 1e9,
                  "every_key": sorted((dev.memory_stats() or {}))}), flush=True)
stats("start")
table = R.table(cfg)
params = C.make_weights(table, seed, low_dtype="bfloat16")
jax.block_until_ready(params)
stats("after make_weights (params)")
zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
m, v = zeros(params), zeros(params)
grads = zeros(params)
jax.block_until_ready((m, v, grads))
stats("after m, v and the gradient sum (16 bytes a parameter)")
rng = jax.random.key(seed & 0x7FFFFFFF)
ids = jax.random.randint(rng, (1, SEQ), 0, cfg["data_vocab_size"], jnp.int32)
rows = (ids, jnp.roll(ids, -1, axis=1))


def attempt(label):
    """Compile grad_block as train_three_steps does, then load and run it."""
    global grads
    loss_rows = R.train_loss_rows(cfg, {"rows": 1, "seq_len": SEQ}, C.Matmul("highest"))

    def grad_block(params, acc, rows):
        (_, parts), g = jax.value_and_grad(lambda p: loss_rows(p, *rows), has_aux=True)(params)
        return jax.tree.map(jnp.add, acc, g), parts

    t = time.time()
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(grad_block, donate_argnums=1).lower(params, grads, rows).compile()
        ma = compiled.memory_analysis()
        print(json.dumps({"attempt": label, "QUERY_BLOCK": R.QUERY_BLOCK, "compile_s": round(time.time() - t, 1),
                          "temp_GB": ma.temp_size_in_bytes / 1e9, "args_GB": ma.argument_size_in_bytes / 1e9,
                          "out_GB": ma.output_size_in_bytes / 1e9, "alias_GB": ma.alias_size_in_bytes / 1e9,
                          "code_GB": ma.generated_code_size_in_bytes / 1e9}), flush=True)
        stats(f"{label}: compiled, before the first call")
        try:
            t = time.time()
            grads, parts = compiled(params, grads, rows)
            loss = float(sum(parts))
            print(json.dumps({"attempt": label, "ran": True, "loss": loss, "run_s": round(time.time() - t, 1)}), flush=True)
            stats(f"{label}: after the call")
            return True
        except Exception as e:          # noqa: BLE001
            print(json.dumps({"attempt": label, "ran": False, "error": str(e)[:400]}), flush=True)
            stats(f"{label}: after the failure")
            return False


ok = attempt("as train_three_steps holds the state")
if not ok:
    jax.clear_caches()
    gc.collect()
    stats("after jax.clear_caches() and gc")
    ok = attempt("caches cleared")
for q in (256, 128):
    if ok:
        break
    R.QUERY_BLOCK = q
    ok = attempt(f"QUERY_BLOCK {q}")
if not ok:
    R.QUERY_BLOCK = 512
    del m, v
    gc.collect()
    stats("the two moments freed")
    attempt("without the moments (not what train_three_steps does)")
