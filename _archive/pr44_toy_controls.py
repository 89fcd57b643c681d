"""Scratch (not committed): on the CPU, tiny-laguna: per seed the sound
program's numbers, the fp8 control's and the two faults'."""
import json, os, sys
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT); sys.path.insert(0, os.path.join(ROOT, "tests", "perf")); sys.path.insert(0, os.path.join(ROOT, "_archive"))
import perf_testlib as L
from perf import loader, traffic_gen, check
from pr44_faults import FAULTS
drv = loader.module("drivers", "train_loop")
for seed in [int(s) for s in sys.argv[1].split(",")]:
    traffic = loader.data("traffic", "pretrain_lm_1x8192")
    traffic["batch"].update(rows=2, seq_len=32); traffic["distinct_batches"] = 6
    limits = {k: {"limit": 1e9} for k in ("loss_gap_step1","loss_gap_step2","loss_gap_step3","first_grad_norm_gap","first_grad_sketch_gap","param_change_norm_gap")}
    ctx = L.context(L.tiny("tiny-laguna"), traffic, limits, seed=seed, seconds=0.3)
    pool = traffic_gen.train_batches(ctx.traffic["batch"], ctx.cfg["data_vocab_size"], seed, 3)
    ref = drv.reference_steps(ctx, pool)
    row = {"seed": seed, "fp8": drv.numbers(drv.reference_steps(ctx, pool, "fp8"), ref)}
    for name in (None, *FAULTS):
        program = ctx.models.build_train(ctx.cfg, ctx.traffic["batch"])
        if name: assert FAULTS[name](program)
        row[name or "sound"] = drv.numbers(drv.checked_steps(ctx, program, pool), ref)
    print(json.dumps(row), flush=True)
