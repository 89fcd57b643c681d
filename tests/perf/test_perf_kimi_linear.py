"""The kimi-linear-48b-a3b configuration and its cell: the file against
BENCHMARK.json, the published widths and its own arithmetic, the chunk
kernels' cost against a hand count, the four new readers on a synthetic
phase table, and the training driver end to end on a toy of the
family."""
import json
import math
import os

import pytest
import perf_testlib as L

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

CONFIG = "kimi-linear-48b-a3b"
CELL = "kimi-linear-48b-a3b.pretrain_8k"
METRICS = ("linear_attention_device_ms.train", "kda_chunk_device_ms.train",
           "kda_glue_device_ms.train", "kda_chunk_roofline.train")
MOONLIGHT_S = ("latent_attention_device_ms.train",
               "latent_glue_device_ms.train", "shared_expert_device_ms.train",
               "routed_here_share.train")
SHARED = ("train_tokens_per_s", "dispatch_ms.train", "input_ms.train",
          "step_device_ms.train", "device_idle_share.train",
          "flash_attention_roofline.train")
BATCH = {"rows": 1, "seq_len": 8192}


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(L.ROOT, "perf", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def _adapter():
    return loader.module("models", "kimi_linear")


def test_parameters_from_the_table(cfg):
    table = loader.module("reference", "kimi_linear").table(cfg)
    count = sum(math.prod(shape) for shape, _, _ in table.values())
    assert count == cfg["parameters"] == 602_433_408
    by = {}
    for name, (shape, _, _) in table.items():
        key = name.split(".", 2)[-1] if name.startswith("layers.") else name
        by[key] = by.get(key, 0) + math.prod(shape)
    kda = sum(v for k, v in by.items() if k.startswith("kda."))
    assert kda == 4 * 39_514_272                # four KDA operators
    assert kda // 4 == (3 * 2304 * 4096 + 3 * 4 * 4096     # q, k, v + taps
                        + 2 * (2304 * 128 + 128 * 4096)     # both necks
                        + 32 + 4096 + 2304 * 32 + 128       # A_log .. o_norm
                        + 4096 * 2304)
    assert sum(v for k, v in by.items() if k.startswith("attn.")) \
        == 29_114_880                           # one latent attention
    assert sum(v for k, v in by.items() if k.startswith("mlp.")) \
        == 3 * 2304 * 9216
    assert sum(v for k, v in by.items() if k.startswith("moe.w")) \
        == 4 * 8 * 7_077_888                    # 226M in held experts
    assert sum(v for k, v in by.items() if k.startswith("shared.")) \
        == 4 * 7_077_888
    assert by["moe.router"] == 4 * 2304 * 256
    assert by["embed"] == by["head"] == 20480 * 2304    # untied
    # this repo's step state, 14 bytes a parameter (PERF.md section 7)
    assert round(14 * count / 1e9, 2) == 8.43


def test_the_file_against_the_benchmark_and_the_published_widths(cfg):
    bench = loader.benchmark()
    entry = loader.by_name(bench["configs"], CONFIG, "config")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
        "blob/main/config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert "32 chips share each layer" in cfg["deployment"]
    cell = loader.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_lm_1x8192", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # every width as published; only depth, experts held and vocabulary
    # rows are this chip's share
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_shared_experts"],
            cfg["num_experts_per_token"], cfg["routed_scaling_factor"]) == (
        2304, 32, 128, 64, 128, 512, 9216, 1024, 1, 8, 2.446)
    assert cfg["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                "vocab_size": 163840}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["data_vocab_size"]) == (5, 8, 20480, 20480)
    # within the guide's floors: the dense layer + four, one whole
    # 3 : 1 period among them, 8 experts, an eighth of the vocabulary
    plan = loader.module("reference", "kimi_linear").plan(cfg)
    assert plan == [("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
                    ("mla", "sparse"), ("kda", "sparse")]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("kda_equations", "kda_projections", "kda_gate", "kda_output",
                "A_log_dt_bias", "mla_use_nope", "router_scores",
                "expert_bias", "moe_renormalize", "shared_expert",
                "initializer", "kda_chunk", "recompute_policy"):
        assert key in cfg["assumed"]
    prec = cfg["precision"]["train"]
    assert prec == {**loader.data("configs", "moonlight-16b-a3b")[
        "precision"]["train"], "recompute_policy": prec["recompute_policy"]}


def test_the_catalog_keys_are_all_there_and_only_the_reduced_differ(cfg):
    """Every number of the published config under its own key, the
    nested group whole."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


def test_kda_chunk_cost_against_a_hand_count(cfg):
    A = _adapter()
    shape = A.kda_shape(cfg, BATCH)
    assert shape == dict(b=1, h=32, s=8192, dk=128, dv=128, chunk=128)
    cost = loader.module("kernel_costs", "kda_chunk")
    chunks = 32 * 64                            # heads x chunks a row
    # a chunk, forward: q k^T and (b k) k^T over the lower triangle,
    # the solve and the scores applied to 128-wide values, three
    # products of 128 rows with the 128 x 128 state
    per_chunk = (2 * 128 * 128 * 128 + 128 * 128 * 128 + 128 * 128 * 128
                 + 3 * 2 * 128 * 128 * 128)
    assert per_chunk == 20_971_520
    fwd_bytes = 32 * 8192 * (2 * (128 + 128 + 128 + 128) + 4 * 128 + 4)
    bwd_bytes = 32 * 8192 * (2 * (5 * 128 + 3 * 128) + 2 * 4 * 128 + 2 * 4)
    assert cost.fwd(**shape) == (chunks * per_chunk, fwd_bytes)
    assert cost.bwd(**shape) == (2 * chunks * per_chunk, bwd_bytes)
    # bound by the memory on a v5e, forward and backward alike
    from perf import readers
    peaks = loader.peaks("TPU v5 lite")
    for need in (cost.fwd, cost.bwd):
        assert readers.least_seconds(*need(**shape), peaks)[1] == "bandwidth"


def test_model_flops_count_eight_slots_by_the_held_share(cfg):
    A = _adapter()
    got = A.train_flops_per_token(cfg, BATCH)
    h, expert = 2304, 3 * 2304 * 1024
    kda = 4 * h * 4096 + 2 * (h * 128 + 128 * 4096) + h * 32
    mla = h * 32 * 192 + h * 576 + 512 * 32 * 256 + 32 * 128 * h
    assert mla == 29_114_880 - 512
    outside = (20480 * h + 4 * kda + mla
               + 3 * h * 9216                   # the dense MLP
               + 4 * (h * 256 + expert))        # routers, shared experts
    slots = 4 * 8 * (8 / 256)                   # 4 layers x top-8 x 1/32
    attention = 3 * 32 * (192 + 128) * 8192     # causal: half of 6
    scan = 4 * 3 * 32 * 64 * 20_971_520 / 8192  # fwd + 2 x fwd, a token
    assert got == pytest.approx(
        6.0 * (outside + slots * expert) + attention + scan)
    assert got == pytest.approx(2.33e9, rel=5e-3)
    assert A.routed_share(cfg) == 1 / 32
    assert A.attention_shape(cfg, BATCH) == dict(
        b=1, h=32, sq=8192, sk=8192, d=160, causal=True)


# ----------------------------------------- readers on a synthetic table
class _Models:
    def __init__(self, tokens, shares, kda=True):
        self.expert_counters = lambda: (tokens, shares)
        if kda:
            self.kda_shape = lambda cfg, batch: dict(
                b=1, h=1, s=64, dk=16, dv=16, chunk=64)


class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 1, "seq_len": 128}}
    cfg = {}


P = "jit(train_step)/KimiLinearForCausalLM/model/layer_2"
B = ("jit(train_step)/backward/KimiLinearForCausalLM/model/layer_2/"
     "transpose(jvp(backward))/KimiLinearForCausalLM/model/layer_2/jvp()/"
     "checkpoint")
Q = "jit(train_step)/KimiLinearForCausalLM/model/layer_3"
OPS = [     # (event's kernel, op_name, duration in ns) of one step
    ("fusion", f"{P}/checkpoint/linear_attention/qkv_conv/q_proj/dot_general",
     400),
    ("fusion", f"{P}/checkpoint/linear_attention/qkv_conv/mul", 60),
    ("fusion", f"{P}/checkpoint/linear_attention/decay_gate/f_a/dot_general",
     100),
    ("fusion", f"{P}/checkpoint/linear_attention/decay_gate/exp", 20),
    ("fusion", f"{P}/checkpoint/linear_attention/kda_chunk/cumsum", 30),
    ("kda_chunk_fwd", f"{P}/checkpoint/linear_attention/kda_chunk/pallas_call",
     1000),
    ("fusion", f"{P}/checkpoint/linear_attention/out_gate_norm/mul", 50),
    ("fusion", f"{P}/checkpoint/linear_attention/o_proj/dot_general", 200),
    ("fusion", f"{Q}/checkpoint/latent_attention/q_proj/dot_general", 300),
    ("flash_attention_fwd", f"{Q}/checkpoint/latent_attention/pallas_call",
     700),
    ("fusion", f"{P}/checkpoint/shared_expert/gate_proj/dot_general", 300),
    ("fusion", f"{P}/checkpoint/routed_experts/router/dot_general", 60),
    ("fusion", f"{P}/checkpoint/mlp/up_proj/dot_general", 900),
    ("kda_chunk_bwd", f"{B}/linear_attention/kda_chunk/pallas_call", 3000),
    ("fusion", f"{B}/linear_attention/kda_chunk/reduce_sum", 150),
    ("fusion", f"{B}/linear_attention/qkv_conv/k_proj/transpose", 250),
    ("fusion", f"{B}/shared_expert/down_proj/transpose", 600),
    ("fusion", "jit(train_step)/optimizer/mul", 40),
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


def _read(run, metrics=METRICS):
    return {m: loader.module("metrics", m).read(run) for m in metrics}


def test_the_four_readers_on_a_synthetic_table(monkeypatch):
    run, raw = _synthetic_run(_Models({"layer_2": [30, 10]},
                                      {"layer_2": 0.25}))
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    got = _read(run)
    glue = 400 + 60 + 100 + 20 + 30 + 50 + 200 + 150 + 250
    assert got["kda_chunk_device_ms.train"] == pytest.approx(4000e-6)
    assert got["kda_glue_device_ms.train"] == pytest.approx(glue * 1e-6)
    assert got["linear_attention_device_ms.train"] == pytest.approx(
        (glue + 4000) * 1e-6)
    # the toy shape's least time: one chunk of 64 x 16, bound by the
    # toy peaks' 1e12 operations a second (12,544 bytes at 1e11 take an
    # eighth of that), forward once and twice that backward, a call of
    # each a step
    flops = 2 * 64 * 64 * 16 + 2 * 64 * 64 * 16 + 6 * 64 * 16 * 16
    assert got["kda_chunk_roofline.train"] == pytest.approx(
        100 * (3 * flops / 1e12) / 4000e-9)
    assert any('"kda_chunk_bound": {"fwd": "compute"' in n
               for n in run.notes)
    # the other family's readers read this cell unchanged
    other = _read(run, MOONLIGHT_S)
    assert other["latent_attention_device_ms.train"] == pytest.approx(1000e-6)
    assert other["latent_glue_device_ms.train"] == pytest.approx(300e-6)
    assert other["shared_expert_device_ms.train"] == pytest.approx(900e-6)
    assert other["routed_here_share.train"] == pytest.approx(0.25)
    # and so do the routed block's parts, LFM2's readers
    block = _read(run, L.BLOCK_PARTS)
    assert block["router_device_ms.train"] == pytest.approx(60e-6)
    assert block["expert_dispatch_device_ms.train"] is None    # none traced
    assert block["expert_mlp_device_ms.train"] == pytest.approx(700e-6)


def test_the_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    """The parent commit's program on another family: no scope and no
    kernel of this family in the trace, no ``kda_shape`` in the adapter.
    Each reader returns None and raises nothing; so does each on an
    untraced run."""
    other = [("fusion",
              "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/mul", 100),
             ("flash_attention_bwd",
              "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/attn/"
              "pallas_call", 300)]
    run, raw = _synthetic_run(object(), other)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    assert _read(run) == dict.fromkeys(METRICS)
    untraced = common.Run(_Ctx())
    untraced.ctx.models = object()
    assert _read(untraced) == dict.fromkeys(METRICS)
    # the kernels in the trace and no shape function in the adapter
    run, raw = _synthetic_run(_Models({}, {}, kda=False))
    monkeypatch.setattr(pr, "load", lambda path: raw)
    assert loader.module("metrics", "kda_chunk_roofline.train").read(
        run) is None


@pytest.mark.parametrize("metric", METRICS + MOONLIGHT_S)
def test_each_new_reader_is_found_by_name_and_its_entry_is_the_cell_s(metric):
    """This family's four readers, and the other family's four, which
    read this cell unchanged."""
    assert callable(loader.module("metrics", metric).read)
    entry = loader.by_name(loader.benchmark()["per_layer"], metric, "metric")
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_tokens_per_s"
    assert (entry["source"], entry["unit"], entry["better"]) == (
        ("program_counter", "ratio", "lower")
        if metric.startswith("routed_here")
        else ("device_trace", "%", "higher")
        if metric.endswith("roofline.train")
        else ("device_trace", "ms", "lower"))
    if metric in METRICS:
        assert entry["workloads"][0] == CELL
        assert entry["layer"] == (
            "kernels: ops/pallas/" if metric.endswith("roofline.train")
            else "model step: models/kimi_linear.py, ops/pallas/kda.py")


@pytest.mark.parametrize("metric", SHARED)
def test_the_cell_is_appended_to_the_lists_it_shares(metric):
    bench = loader.benchmark()
    entry = loader.by_name(bench["end_to_end"] + bench["per_layer"], metric,
                           "metric")
    # the cells that were there stay first; a later cell may follow
    assert entry["workloads"][:4] == [
        "gpt2-medium.pretrain", "lfm2-24b-a2b.pretrain_8k",
        "moonlight-16b-a3b.pretrain_8k", CELL]


def test_the_cell_is_in_every_list_that_reads_it_and_has_its_limits():
    bench = loader.benchmark()
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ())}
    # a later PR may declare more for the cell: no upper end
    assert listed >= set(SHARED) | set(METRICS) | set(MOONLIGHT_S) \
        | set(L.EVERY_STEP) | set(L.EVERY_BLOCK)
    limits = loader.data("limits", CELL)
    assert set(limits) == {"loss_gap_step1", "loss_gap_step2",
                           "loss_gap_step3", "first_grad_norm_gap",
                           "first_grad_sketch_gap", "param_change_norm_gap"}
    for name, entry in limits.items():
        assert entry["limit"] > 0 and "PR 37" in entry["set_from"], name


# ------------------------------------------------ the driver, on a toy
@pytest.fixture(scope="module")
def toy():
    """The ``moe.*`` gauges and the rings are the process's: what
    another file's tests left is put aside while this one's run, and
    what these leave is taken away after them (as
    ``test_perf_moonlight.py``)."""
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    reg = metrics.registry()

    def take_moe():
        return {k: reg._metrics.pop(k) for k in list(reg._metrics)
                if k[0].startswith("moe.")}

    import paddle_tpu as paddle
    gauges, rings = take_moe(), dict(moe._calls_of)
    moe._calls_of.clear()
    # the program's flag the adapter sets from the configuration
    fused = paddle.get_flags("fused_opt")["fused_opt"]
    traffic = loader.data("traffic", "pretrain_lm_1x8192")
    traffic["batch"].update(rows=2, seq_len=32)
    traffic["distinct_batches"] = 6
    yield L.context(L.tiny("tiny-kimi_linear"), traffic,
                    L.tiny("limits-tiny-kimi_linear-train"), seed=2,
                    seconds=0.3)
    paddle.set_flags({"fused_opt": fused})
    take_moe()
    reg._metrics.update(gauges)
    moe._calls_of.clear()
    moe._calls_of.update(rings)


def test_the_toy_keeps_both_operators_and_counts_its_parameters(toy):
    cfg = toy.cfg
    table = toy.reference.table(cfg)
    assert sum(math.prod(s) for s, _, _ in table.values()) \
        == cfg["parameters"]
    assert toy.reference.plan(cfg) == [("kda", "dense"), ("mla", "sparse")]


def test_train_loop_end_to_end_on_the_toy(toy):
    run = loader.module("drivers", "train_loop").run(toy)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert any('"programs_compiled_in_window": 0' in n for n in run.notes)
    assert any('"step_programs": 1' in n for n in run.notes)
    # the counters the readers use were fed by the compiled step
    tokens, shares = toy.models.expert_counters()
    steps = 3 + 2 + run.attempted
    assert sum(tokens["layer_1"]) == pytest.approx(
        shares["layer_1"] * steps * 2 * 32 * 3)
    share = loader.module("metrics", "routed_here_share.train").read(run)
    assert share == pytest.approx(shares["layer_1"])
    assert 0 < share < 1


def test_the_parameters_change_is_measured_from_the_values_as_held(toy):
    """``make_weights`` rounds a leaf to the compute type by a pair of
    conversions, which the TPU's compiler takes out: there the seeded
    values come unrounded, and the base ``TrainProgram`` reads a
    parameter's ROUNDING as its change.  The adapter's program rounds
    the initial values explicitly: seeded values a quarter of a
    bfloat16 step off read as no change at all."""
    from perf.models import common as M
    program = toy.models.build_train(toy.cfg, toy.traffic["batch"])
    state = loader.module("drivers", "train_loop").seeded_state(toy, program)
    M.load_weights(program.model, state)
    unrounded = {n: v * (1 + 2.0 ** -11) for n, v in state.items()}
    low = program.low_leaves()
    assert low == set(state)
    base = M.TrainProgram.param_change_norms(program, unrounded)
    held = program.param_change_norms(unrounded)
    assert all(v > 0 for v in base.values())
    assert set(held) == set(base) and all(v == 0 for v in held.values())
