"""The moonlight-16b-a3b configuration and its cell: the file against
BENCHMARK.json, the published widths and its own arithmetic, the flash
kernels' cost at two widths, the four new readers on a synthetic phase
table, and the training driver end to end on a toy of the family."""
import json
import math
import os

import pytest
import perf_testlib as L

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

CONFIG = "moonlight-16b-a3b"
CELL = "moonlight-16b-a3b.pretrain_8k"
METRICS = ("latent_attention_device_ms.train", "latent_glue_device_ms.train",
           "shared_expert_device_ms.train", "routed_here_share.train")
SHARED = ("train_tokens_per_s", "dispatch_ms.train", "input_ms.train",
          "step_device_ms.train", "device_idle_share.train",
          "flash_attention_roofline.train")
BATCH = {"rows": 1, "seq_len": 8192}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(L.ROOT, "perf", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def _adapter():
    return loader.module("models", "deepseek_v3")


def test_parameters_from_the_table(cfg):
    table = loader.module("reference", "deepseek_v3").table(cfg)
    count = sum(math.prod(shape) for shape, _, _ in table.values())
    assert count == cfg["parameters"] == 568_484_352
    by = {}
    for name, (shape, _, _) in table.items():
        key = name.split(".", 2)[-1] if name.startswith("layers.") else name
        by[key] = by.get(key, 0) + math.prod(shape)
    operator = sum(v for k, v in by.items() if k.startswith("attn."))
    assert operator == 5 * 13_763_072           # five latent attentions
    assert sum(v for k, v in by.items() if k.startswith("moe.w")) \
        == 4 * 8 * 8_650_752                    # 277M in held experts
    assert sum(v for k, v in by.items() if k.startswith("shared.")) \
        == 4 * 17_301_504
    assert by["embed"] == by["head"] == 20480 * 2048    # untied
    # this repo's step state, 22 bytes a parameter (PERF.md section 7)
    assert round(22 * count / 1e9, 2) == 12.51


def test_the_file_against_the_benchmark_and_the_published_widths(cfg):
    bench = loader.benchmark()
    entry = loader.by_name(bench["configs"], CONFIG, "config")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
        "config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert "8 chips share each layer" in cfg["deployment"]
    cell = loader.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_lm_1x8192", 1)
    traffic = loader.data("traffic", cell["traffic"])
    assert traffic["batch"] == {"task": "causal_lm", **BATCH}
    assert {k: v for k, v in traffic.items() if k not in ("batch", "why")} \
        == {k: v for k, v in loader.data(
            "traffic", "pretrain_lm_2x8192").items()
            if k not in ("batch", "why")}
    # every width as published; only depth, experts held and vocabulary
    # rows are this chip's share
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["n_shared_experts"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2048, 16, 128, 64, 128, 512, 11264, 1408, 2, 6, 2.446)
    assert (cfg["q_lora_rank"], cfg["first_k_dense_replace"],
            cfg["rope_theta"], cfg["rms_norm_eps"], cfg["scoring_func"],
            cfg["topk_method"], cfg["n_group"], cfg["topk_group"],
            cfg["tie_word_embeddings"], cfg["max_position_embeddings"]) == (
        None, 1, 50000, 1e-5, "sigmoid", "noaux_tc", 1, 1, False, 8192)
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "n_routed_experts": 64, "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["data_vocab_size"]) == (5, 8, 20480, 20480)
    plan = loader.module("reference", "deepseek_v3").plan(cfg)
    assert plan == ["dense"] + 4 * ["sparse"]
    for key in ("rope", "kv_norm_eps", "router_scores", "expert_bias",
                "norm_topk_prob", "shared_expert", "seq_aux", "initializer",
                "expert_bias_seed"):
        assert key in cfg["assumed"]
    prec = cfg["precision"]["train"]
    assert (prec["compute"], prec["amp_level"], prec["optimizer"],
            prec["flash_attention"], prec["fused_optimizer"]) == (
        "bfloat16", "O2", "AdamW", True, False)
    assert prec == {**loader.data("configs", "lfm2-24b-a2b")[
        "precision"]["train"], "recompute_policy": prec["recompute_policy"]}


def test_the_catalog_keys_are_all_there_and_only_the_reduced_differ(cfg):
    """Every number of the published config under its own key."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 11264, "kv_lora_rank": 512,
        "max_position_embeddings": 8192, "model_type": "deepseek_v3",
        "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 2,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 6, "num_hidden_layers": 27,
        "num_key_value_heads": 16, "num_nextn_predict_layers": 0,
        "q_lora_rank": None, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 50000,
        "routed_scaling_factor": 2.446, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 163840}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


def test_flash_cost_at_the_mean_width_is_the_count_product_by_product(cfg):
    """``kernel_costs/flash_attention`` takes one width; at the mean of
    192 and 128 its operations and bytes equal the count written out
    for the two widths."""
    A = _adapter()
    shape = A.attention_shape(cfg, BATCH)
    assert shape == dict(b=1, h=16, sq=8192, sk=8192, d=160, causal=True)
    cost = loader.module("kernel_costs", "flash_attention")
    s, heads, qk, dv = 8192, 16, 192, 128
    # forward: q k^T over 192 and p v over 128, half the tiles
    fwd_flops = heads * (2 * s * s * qk + 2 * s * s * dv) / 2
    # backward: dv = p^T do and dp = do v^T over 128, dq = ds k and
    # dk = ds^T q over 192
    bwd_flops = heads * (2 * 2 * s * s * dv + 2 * 2 * s * s * qk) / 2
    row_stats = 4 * heads * s
    fwd_bytes = 2 * heads * s * (qk + qk + dv + dv) + row_stats
    bwd_bytes = 2 * heads * s * ((qk + qk + dv + dv + dv)      # q k v o do
                                 + (qk + qk + dv)) + row_stats  # dq dk dv
    assert cost.fwd(**shape) == (fwd_flops, fwd_bytes)
    assert cost.bwd(**shape) == (bwd_flops, bwd_bytes)


def test_model_flops_count_six_slots_by_the_held_share_and_the_shared_whole(
        cfg):
    A = _adapter()
    got = A.train_flops_per_token(cfg, BATCH)
    h, expert = 2048, 3 * 2048 * 1408
    operator = h * 16 * 192 + h * 576 + 512 * 16 * 256 + 16 * 128 * h
    assert operator == 13_763_072 - 512
    outside = (20480 * h                        # the untied head
               + 5 * operator
               + 3 * h * 11264                  # the dense MLP
               + 4 * (h * 64 + 2 * expert))     # routers, shared experts
    slots = 4 * 6 * (8 / 64)                    # 4 layers x top-6 x 1/8
    attention = 5 * 3 * 16 * (192 + 128) * 8192     # causal: half of 6
    assert got == pytest.approx(6.0 * (outside + slots * expert) + attention)
    assert got == pytest.approx(2.283e9, rel=1e-3)
    assert attention / got == pytest.approx(0.2756, abs=1e-3)
    assert A.routed_share(cfg) == 0.125


# ----------------------------------------- readers on a synthetic table
class _Models:
    def __init__(self, tokens, shares):
        self.expert_counters = lambda: (tokens, shares)


class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 1, "seq_len": 100}}
    cfg = {}


P = "jit(train_step)/DeepseekV3ForCausalLM/model/layer_2"
B = ("jit(train_step)/backward/DeepseekV3ForCausalLM/model/layer_2/"
     "transpose(jvp(backward))/DeepseekV3ForCausalLM/model/layer_2/jvp()/"
     "checkpoint")
OPS = [     # (event's kernel, op_name, duration in ns) of one step
    ("fusion", f"{P}/checkpoint/latent_attention/q_proj/dot_general", 400),
    ("fusion", f"{P}/checkpoint/latent_attention/kv_down/dot_general", 100),
    ("fusion", f"{P}/checkpoint/latent_attention/kv_norm/mul", 20),
    ("fusion", f"{P}/checkpoint/latent_attention/kv_up/dot_general", 200),
    ("fusion", f"{P}/checkpoint/latent_attention/rope/select_n", 30),
    ("copy", f"{P}/checkpoint/latent_attention/assemble/concatenate", 50),
    ("flash_attention_fwd",
     f"{P}/checkpoint/latent_attention/pallas_call", 1000),
    ("fusion", f"{P}/checkpoint/shared_expert/gate_proj/dot_general", 300),
    ("fusion", f"{P}/checkpoint/routed_experts/router/dot_general", 60),
    ("fusion", f"{P}/checkpoint/routed_experts/dispatch/sort", 70),
    ("fusion", f"{P}/checkpoint/routed_experts/expert_mlp/while/body/"
     "ragged_dot_general", 500),
    ("fusion", f"{P}/checkpoint/routed_experts/combine/gather", 80),
    ("fusion", f"{P}/checkpoint/mlp/up_proj/dot_general", 900),
    ("flash_attention_bwd", f"{B}/latent_attention/pallas_call", 2000),
    ("fusion", f"{B}/latent_attention/assemble/pad", 150),
    ("fusion", f"{B}/latent_attention/o_proj/transpose", 250),
    ("fusion", f"{B}/shared_expert/down_proj/transpose", 600),
    ("fusion", f"{B}/routed_experts/dispatch/gather", 90),
    ("fusion", "jit(train_step)/optimizer/mul", 40),
    # the compiler's grouped-product kernel: its op_name is its own
    ("ragged-dot-none", "ragged-dot-none", 700),
]


def _synthetic_run(models, ops=OPS):
    events, names, at = [], [], 1000
    for _ in range(2):                          # two steps traced
        for i, (kernel, op, ns) in enumerate(ops):
            events.append([f"%{kernel}.{i} f32[8]", at, ns])
            names.append(op)
            at += ns + 10
    host = [["train_step", 900, at], ["to_static.call", 950, 100],
            ["to_static.call", 5000, 100]]
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": events, "op_names": names}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    ctx = _Ctx()
    ctx.models = models
    run = common.Run(ctx)
    run.trace = tr.Trace({"planes": [
        {"name": p["name"], "lines": [{"name": ln["name"],
                                       "events": ln["events"]}
                                      for ln in p["lines"]]}
        for p in raw["planes"]]})
    return run, raw


def _read_all(run, metrics=METRICS):
    return {m: loader.module("metrics", m).read(run) for m in metrics}


def test_the_four_readers_on_a_synthetic_table(monkeypatch):
    tokens = {"layer_1": [30, 10], "layer_2": [50, 30]}
    shares = {"layer_1": 0.10, "layer_2": 0.20}
    run, raw = _synthetic_run(_Models(tokens, shares))
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    got = _read_all(run)
    glue = 400 + 100 + 20 + 200 + 30 + 50 + 150 + 250
    assert got["latent_glue_device_ms.train"] == pytest.approx(glue * 1e-6)
    assert got["latent_attention_device_ms.train"] == pytest.approx(
        (glue + 1000 + 2000) * 1e-6)
    assert got["shared_expert_device_ms.train"] == pytest.approx(900e-6)
    assert got["routed_here_share.train"] == pytest.approx(0.15)
    # the routed block by its parts, through LFM2's readers unchanged
    block = _read_all(run, L.BLOCK_PARTS)
    assert block["router_device_ms.train"] == pytest.approx(60e-6)
    assert block["expert_dispatch_device_ms.train"] == pytest.approx(
        (70 + 80 + 90) * 1e-6)
    assert block["expert_mlp_device_ms.train"] == pytest.approx(
        (500 + 700) * 1e-6)
    assert any('"routed_here_share": {"layer_1": 0.1' in n
               for n in run.notes)


def test_the_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    """The parent commit's program on another family: no scope of this
    family in the trace, no counter in the adapter.  Each reader
    returns None and raises nothing; so does each on an untraced run."""
    other = [("fusion",
              "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/mul", 100),
             ("flash_attention_bwd",
              "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/attn/"
              "pallas_call", 300)]
    run, raw = _synthetic_run(object(), other)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    assert _read_all(run) == dict.fromkeys(METRICS)
    untraced = common.Run(_Ctx())
    untraced.ctx.models = object()
    assert _read_all(untraced) == dict.fromkeys(METRICS)
    # counters whose read failed give nothing either
    run, _ = _synthetic_run(_Models({"layer_1": [1, 2]}, {"layer_1": None}))
    assert loader.module("metrics", "routed_here_share.train").read(
        run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_each_new_reader_is_found_by_name_and_its_entry_is_the_cell_s(metric):
    assert callable(loader.module("metrics", metric).read)
    entry = loader.by_name(loader.benchmark()["per_layer"], metric, "metric")
    # a later cell may be appended: no pin to the cell alone
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["layer"] == ("model step: models/deepseek_v3.py, "
                              "incubate/distributed/models/moe.py")
    assert (entry["source"], entry["unit"]) == (
        ("program_counter", "ratio") if metric.startswith("routed_here")
        else ("device_trace", "ms"))


@pytest.mark.parametrize("metric", SHARED)
def test_the_cell_is_appended_to_the_lists_it_shares(metric):
    bench = loader.benchmark()
    entry = loader.by_name(bench["end_to_end"] + bench["per_layer"], metric,
                           "metric")
    # the cells that were there stay first; a later cell may follow
    assert entry["workloads"][:3] == ["gpt2-medium.pretrain",
                                      "lfm2-24b-a2b.pretrain_8k", CELL]


def test_the_cell_is_in_every_list_that_reads_it_and_has_its_limits():
    bench = loader.benchmark()
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    # a later PR may declare more for the cell: no upper end
    assert listed >= set(SHARED) | set(METRICS) | set(L.EVERY_STEP) \
        | set(L.EVERY_BLOCK)
    limits = loader.data("limits", CELL)
    assert set(limits) == {"loss_gap_step1", "loss_gap_step2",
                           "loss_gap_step3", "first_grad_norm_gap",
                           "first_grad_sketch_gap", "param_change_norm_gap"}
    for name, entry in limits.items():
        assert entry["limit"] > 0 and "PR 35" in entry["set_from"], name


# ------------------------------------------------ the driver, on a toy
@pytest.fixture(scope="module")
def toy():
    """The toy's sparse layers are ``layer_1`` and ``layer_2``, as the
    other family's toy's (``test_perf_lfm2.py``), and the ``moe.*``
    gauges and the rings are the process's: what another file's tests
    left is put aside while this one's run, and what these leave is
    taken away after them, so neither file reads the other's experts
    whichever runs first in a worker."""
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    reg = metrics.registry()

    def take_moe():
        return {k: reg._metrics.pop(k) for k in list(reg._metrics)
                if k[0].startswith("moe.")}

    gauges, rings = take_moe(), dict(moe._calls_of)
    moe._calls_of.clear()
    traffic = loader.data("traffic", "pretrain_lm_1x8192")
    traffic["batch"].update(rows=2, seq_len=32)
    traffic["distinct_batches"] = 6
    yield L.context(L.tiny("tiny-deepseek_v3"), traffic,
                    L.tiny("limits-tiny-deepseek_v3-train"), seed=1,
                    seconds=0.3)
    take_moe()
    reg._metrics.update(gauges)
    moe._calls_of.clear()
    moe._calls_of.update(rings)


def test_the_toy_keeps_the_ratios_and_counts_its_parameters(toy):
    cfg = toy.cfg
    table = toy.reference.table(cfg)
    assert sum(math.prod(s) for s, _, _ in table.values()) \
        == cfg["parameters"]
    assert cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        != cfg["v_head_dim"]
    assert toy.reference.plan(cfg) == ["dense", "sparse", "sparse"]


def test_train_loop_end_to_end_on_the_toy(toy):
    run = loader.module("drivers", "train_loop").run(toy)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert any('"programs_compiled_in_window": 0' in n for n in run.notes)
    assert any('"step_programs": 1' in n for n in run.notes)
    # the counters the readers use were fed by the compiled step
    tokens, shares = toy.models.expert_counters()
    steps = 3 + 2 + run.attempted
    for layer in ("layer_1", "layer_2"):
        assert sum(tokens[layer]) == pytest.approx(
            shares[layer] * steps * 2 * 32 * 3)
    share = loader.module("metrics", "routed_here_share.train").read(run)
    assert share == pytest.approx(sum(shares.values()) / 2)
    assert 0 < share < 1


def test_fp8_control_is_not_correct_on_the_toy(toy):
    from perf import check, traffic_gen
    drv = loader.module("drivers", "train_loop")
    pool = traffic_gen.train_batches(
        toy.traffic["batch"], toy.cfg["data_vocab_size"], toy.seed, 3)
    ref = drv.reference_steps(toy, pool)
    control = check.Checks(toy.limits)
    check.train_checks(control, drv.reference_steps(toy, pool, "fp8"), ref)
    assert not control.correct
    assert not control.as_dict()["first_grad_sketch_gap"]["ok"]
