"""Scratch (not committed): per seed, at the cell's own size on the chip,
the sound program's numbers, the fp8 control's (perf/controls.py's) and two
FAULTS', each put into a program the adapter built anew, before its first
step (_archive/pr44_faults.py): the full layers rotate all 128 dimensions;
the gate left out.  One process, one set-up.

    python3 _archive/pr44_controls.py --seeds 1,2 [--no-fp8]
"""
import argparse, copy, gc, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "_archive"))

ap = argparse.ArgumentParser()
ap.add_argument("--seeds", required=True)
ap.add_argument("--no-fp8", action="store_true")
ap.add_argument("--workload", default="laguna-xs.2.pretrain_8k")
args = ap.parse_args()
seeds = [int(s) for s in args.seeds.split(",")]

from perf import loader, traffic_gen
from perf.run import build_context
from perf.drivers import train_loop as T
from pr44_faults import FAULTS
ctx0, rc = build_context(args.workload, seeds[0], 8.0, 0)
if ctx0 is None:
    sys.exit(rc)
out = open(os.path.join(ROOT, "chiprun_out", "pr44_controls.jsonl"), "a")


def program_numbers(ctx, pool, reference, fault):
    program = ctx.models.build_train(ctx.cfg, ctx.traffic["batch"])
    if fault:
        assert FAULTS[fault](program) in (2, 5), fault
    mine = T.checked_steps(ctx, program, pool)
    numbers = T.numbers(mine, reference)
    del program, mine
    gc.collect()
    return numbers


for seed in seeds:
    ctx = copy.copy(ctx0)
    ctx.seed = seed
    spec, n = ctx.traffic["batch"], ctx.traffic["checked_steps"]
    pool = traffic_gen.train_batches(spec, ctx.cfg["data_vocab_size"], seed, n)
    t = time.time()
    reference = T.reference_steps(ctx, pool)
    row = {"seed": seed, "reference_s": time.time() - t, "reference_losses": reference["losses"]}
    if not args.no_fp8:
        t = time.time()
        row["control"] = T.numbers(T.reference_steps(ctx, pool, "fp8"), reference)
        row["control_s"] = time.time() - t
    for fault in (None, *FAULTS):
        t = time.time()
        row[fault or "sound"] = program_numbers(ctx, pool, reference, fault)
        row[(fault or "sound") + "_s"] = time.time() - t
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n"); out.flush()
