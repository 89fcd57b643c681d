"""Helpers of the perf tests: a driver's context at a tiny size on the
CPU, built the way perf/run.py builds it but without its look for a
chip."""
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the cells with a ``SparseMoEBlock``, in the order they came
SPARSE_CELLS = ["lfm2-24b-a2b.pretrain_8k", "moonlight-16b-a3b.pretrain_8k",
                "kimi-linear-48b-a3b.pretrain_8k"]
# per-layer metrics every training cell reports (test_perf_phases.py,
# test_perf_step_mfu.py) and every cell with a sparse block does
# (test_perf_lfm2.py); the block's first three are its device time by
# parts
EVERY_STEP = ("forward_device_ms.train", "recompute_device_ms.train",
              "backward_device_ms.train", "optimizer_device_ms.train",
              "head_loss_device_ms.train", "unattributed_device_share.train",
              "launch_ms.train", "state_io_ms.train", "step_mfu.train")
EVERY_BLOCK = ("router_device_ms.train", "expert_dispatch_device_ms.train",
               "expert_mlp_device_ms.train", "expert_mlp_roofline.train",
               "expert_load_max_over_mean.train",
               "expert_rows_run_share.train")
BLOCK_PARTS = EVERY_BLOCK[:3]

TRAIN_SHAPE = {"gpt2": dict(rows=4, seq_len=32),
               "bert": dict(rows=4, seq_len=32, masked_per_row=5)}
TRAIN_MIX = {"gpt2": "pretrain_lm_8x1024", "bert": "pretrain_mlm_16x512"}


def tiny(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def context(cfg, traffic, limits, seed, seconds, trace_dir=None):
    import jax

    from perf import loader
    from perf.run import Compiles, Context
    return Context(
        root=ROOT, workload={"name": "tiny", "chips": 1}, cfg=cfg,
        traffic=copy.deepcopy(traffic), limits=limits, seed=seed,
        seconds=seconds, trace=False, trace_dir=trace_dir,
        devices=jax.devices()[:1],
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        compiles=Compiles(), t_start=time.time(),
        models=loader.module("models", cfg["family"]),
        reference=loader.module("reference", cfg["family"]))


def train_context(family, seed, seconds=0.3):
    from perf import loader
    traffic = loader.data("traffic", TRAIN_MIX[family])
    traffic["batch"].update(TRAIN_SHAPE[family])
    traffic["reference_rows_per_block"] = 2
    traffic["distinct_batches"] = 6
    return context(tiny(f"tiny-{family}"), traffic,
                   tiny(f"limits-tiny-{family}-train"), seed, seconds)


def serve_context(mix, seed, seconds=1.5):
    from perf import loader
    traffic = loader.data("traffic", mix)
    rq = traffic["requests"]
    rq["prompt_len"].update(median=12, lo=4, hi=30)
    rq["output_len"].update(median=8, lo=2, hi=20)
    rq["max_total"] = 64
    if "rate" in rq:
        rq["rate"] = 8.0
        traffic["ramp_seconds"] = 0.5
    else:
        rq.update(backlog=16, refill_below=4)
        traffic["ramp_seconds"] = 0.3
    traffic["engine"].update(max_slots=4, page_size=4, decode_window=4,
                             prefill_chunk=16, max_seq_len=64)
    traffic["check_sample"] = 4
    return context(tiny("tiny-gpt2"), traffic,
                   tiny("limits-tiny-gpt2-serve"), seed, seconds)
