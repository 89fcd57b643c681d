"""Scratch (not committed): per seed, the cell's own program from the
adapter, seeded as the driver seeds it, stepped over the window's batches
with no reference and no profiler: per block of ten steps its seconds, and
per layer the slots routed here in every step (``moe.routed_by_call``).

    python3 _archive/pr44_slots.py --seeds 4200000704,4200000705 [--steps 115]
"""
import argparse, copy, gc, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def one_seed(ctx0, seed, steps):
    from perf import traffic_gen
    from perf.drivers import train_loop as T
    from perf.models import common as M
    ctx = copy.copy(ctx0)
    ctx.seed = seed
    spec = ctx.traffic["batch"]
    pool = traffic_gen.train_batches(spec, ctx.cfg["data_vocab_size"], seed, ctx.traffic["distinct_batches"])
    t = time.time()
    program = ctx.models.build_train(ctx.cfg, spec)
    M.load_weights(program.model, T.seeded_state(ctx, program))
    blocks, losses, t0 = [], [], time.perf_counter()
    for i in range(steps):
        loss = program.step(program.feed(pool[i % len(pool)]))
        if (i + 1) % 10 == 0 or i + 1 == steps:
            losses.append(float(loss))
            now = time.perf_counter()
            blocks.append(round(now - t0, 4))
            t0 = now
    calls = ctx.models.expert_calls()
    held = ctx.cfg["num_experts"]
    row = {"seed": seed, "build_s": round(time.time() - t, 1), "block_seconds": blocks, "losses": [round(x, 4) for x in losses]}
    edge = ctx.cfg["expert_slots_at_a_time"] or 8192
    for layer in sorted(calls):
        per = [sum(calls[layer][n][:held]) for n in sorted(calls[layer])]
        row[layer] = {"every_10th": per[::10], "min": min(per), "max": max(per),
                      "steps_over_the_chunk": sum(p > edge for p in per), "steps": len(per),
                      "fullest_expert_max": max(max(calls[layer][n][:held]) for n in calls[layer])}
    del program
    gc.collect()
    return row


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=115)
    ap.add_argument("--workload", default="laguna-xs.2.pretrain_8k")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    from perf.run import build_context
    ctx0, rc = build_context(args.workload, seeds[0], 8.0, 0)
    if ctx0 is None:
        sys.exit(rc)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = open(os.path.join(ROOT, "chiprun_out", "pr44_slots.jsonl"), "a")
    for seed in seeds:
        line = json.dumps(one_seed(ctx0, seed, args.steps))
        print(line, flush=True)
        out.write(line + "\n"); out.flush()
