"""The mellum2-12b-a2.5b configuration and its cell: the file against
BENCHMARK.json, the published widths and its own arithmetic, the band's
pair count against brute force, the three new readers on a synthetic
phase table, and the training driver end to end on a toy of the
family.  About 45 s under the tier-1 command (the toy's run)."""
import json
import math
import os

import numpy as np
import pytest
import perf_testlib as L

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

CONFIG = "mellum2-12b-a2.5b"
CELL = "mellum2-12b-a2.5b.pretrain_8k"
METRICS = ("window_attention_device_ms.train",
           "full_attention_device_ms.train",
           "window_attention_roofline.train")
SHARED = ("train_tokens_per_s", "dispatch_ms.train", "input_ms.train",
          "step_device_ms.train", "device_idle_share.train",
          "flash_attention_roofline.train", "routed_here_share.train")
NOT_ITS = ("short_conv_device_ms.train", "latent_attention_device_ms.train",
           "latent_glue_device_ms.train", "shared_expert_device_ms.train",
           "linear_attention_device_ms.train", "kda_chunk_device_ms.train",
           "kda_glue_device_ms.train", "kda_chunk_roofline.train")
BATCH = {"rows": 1, "seq_len": 8192}
ACCEPTED = ["gpt2-medium.pretrain", *L.SPARSE_CELLS]


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(L.ROOT, "perf", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def _adapter():
    return loader.module("models", "mellum")


def test_parameters_from_the_table(cfg):
    R = loader.module("reference", "mellum")
    table = R.table(cfg)
    count = sum(math.prod(shape) for shape, _, _ in table.values())
    assert count == cfg["parameters"] == R.parameters(cfg) == 624_072_960
    by = {}
    for name, (shape, _, _) in table.items():
        key = name.split(".", 2)[-1] if name.startswith("layers.") else name
        by[key] = by.get(key, 0) + math.prod(shape)
    attention = sum(v for k, v in by.items() if k.startswith("attn."))
    assert attention == 8 * 21_233_664          # q, o 9.44M each; k, v 1.18M
    assert attention // 8 == 2 * 2304 * 4096 + 2 * 2304 * 512
    assert sum(v for k, v in by.items() if k.startswith("moe.w")) \
        == 8 * 8 * 6_193_152                    # 396M in held experts
    assert by["moe.router"] == 8 * 2304 * 64
    assert by["input_norm"] + by["ffn_norm"] == 8 * 4608
    assert by["embed"] == by["head"] == 12288 * 2304    # untied
    assert by["final_norm"] == 2304
    assert not any(k.startswith(("shared.", "mlp.")) for k in by)
    # this repo's step state, 14 bytes a parameter (PERF.md section 7):
    # over half the chip's 16.91 GB
    assert round(14 * count / 1e9, 2) == 8.74


def test_the_file_against_the_benchmark_and_the_published_widths(cfg):
    bench = loader.benchmark()
    entry = loader.by_name(bench["configs"], CONFIG, "config")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/"
        "blob/main/config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert "8 chips share each layer" in cfg["deployment"]
    cell = loader.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_lm_1x8192", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert bench["workloads"][-1] is cell and bench["configs"][-1] is entry
    # every width as published; only depth, experts held and vocabulary
    # rows are this chip's share
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rms_norm_eps"]) == (2304, 32, 4, 128, 1024, 7168, 896, 8,
                                     1e-6)
    assert cfg["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                "vocab_size": 98304}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["data_vocab_size"]) == (8, 8, 12288, 12288)
    # within the guide's floors: two whole periods, 8 experts, an eighth
    # of the vocabulary
    plan = loader.module("reference", "mellum").plan(cfg)
    assert plan == (["sliding_attention"] * 3 + ["full_attention"]) * 2
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    for key in ("qk_norm", "router", "mtp_head", "intermediate_size",
                "initializer", "rope", "sliding_window", "recompute_policy",
                "expert_load", "expert_slots_at_a_time"):
        assert key in cfg["assumed"]
    # the lone share's two choices, and the chunk they leave the even
    # spread's load (8,192 slots a layer) in the middle of
    assert (cfg["train_router"], cfg["expert_slots_at_a_time"]) \
        == (False, 16384)
    even = BATCH["seq_len"] * cfg["num_experts_per_tok"] \
        * cfg["num_experts"] // cfg["published"]["num_experts"]
    assert even == 8192 and even * 2 == cfg["expert_slots_at_a_time"]
    assert loader.module("reference", "mellum").table(cfg)["embed"][2] == 1.0
    prec = cfg["precision"]["train"]
    other = loader.data("configs", "moonlight-16b-a3b")["precision"]["train"]
    assert prec == {**other, "router": prec["router"]}


def test_the_catalog_keys_are_all_there_and_only_the_reduced_differ(cfg):
    """Every number of the published config under its own key, the
    nested group and the two per-layer lists whole."""
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": period * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


@pytest.mark.parametrize("s,window", [(8192, 1024), (64, 16), (64, 1),
                                      (50, 7), (64, 64), (64, 100)])
def test_the_bands_pair_count_against_brute_force(s, window):
    cost = loader.module("kernel_costs", "window_attention")
    if s <= 64:         # every pair, one by one
        i, j = np.arange(s)[:, None], np.arange(s)[None, :]
        brute = int(((j <= i) & (i - j < window)).sum())
    else:               # row by row: position i sees min(i + 1, window)
        brute = int(np.minimum(np.arange(s) + 1, window).sum())
    assert cost.pairs(s, window) == brute
    if (s, window) == (8192, 1024):
        assert brute == 7_864_832       # against 33.55M causal: 0.234
        assert brute / (8192 * 8193 // 2) == pytest.approx(0.2344, rel=1e-3)
    if window >= s:                     # the causal count, diagonal included
        assert brute == s * (s + 1) // 2


def test_window_cost_against_a_hand_count(cfg):
    A = _adapter()
    shape = A.window_shape(cfg, BATCH)
    assert shape == dict(b=1, h=32, kv=4, s=8192, d=128, window=1024)
    cost = loader.module("kernel_costs", "window_attention")
    per_pair = 2 * 32 * 128             # a product's flops a pair, all heads
    fwd_bytes = 2 * 128 * 8192 * (2 * 32 + 2 * 4) + 4 * 32 * 8192
    bwd_bytes = 2 * 128 * 8192 * (4 * 32 + 4 * 4) + 4 * 32 * 8192
    assert cost.fwd(**shape) == (2 * per_pair * 7_864_832, fwd_bytes)
    assert cost.bwd(**shape) == (4 * per_pair * 7_864_832, bwd_bytes)
    # bound by the products on a v5e, a quarter of the full layer's
    from perf import readers
    peaks = loader.peaks("TPU v5 lite")
    for need in (cost.fwd, cost.bwd):
        assert readers.least_seconds(*need(**shape), peaks)[1] == "compute"
    full = loader.module("kernel_costs", "flash_attention")
    assert A.attention_shape(cfg, BATCH) == dict(
        b=1, h=32, sq=8192, sk=8192, d=128, causal=True)
    ratio = cost.fwd(**shape)[0] / full.fwd(**A.attention_shape(cfg, BATCH))[0]
    assert ratio == pytest.approx(7_864_832 / (8192 * 8192 / 2))


def test_model_flops_count_a_window_layer_at_its_band(cfg):
    A = _adapter()
    got = A.train_flops_per_token(cfg, BATCH)
    h, expert = 2304, 3 * 2304 * 896
    # the router's product forward only: ``train_router`` is false
    assert cfg["train_router"] is False
    layer = 2 * h * 4096 + 2 * h * 512 + h * 64 / 3
    slots = 8 * (8 / 64)                        # top-8 x 1/8, a layer
    outside = 12288 * h + 8 * layer
    full = 6 * 2 * 32 * 128 * (8192 * 8192 / 2) / 8192
    band = 6 * 2 * 32 * 128 * 7_864_832 / 8192
    assert got == pytest.approx(
        6.0 * (outside + 8 * slots * expert) + 2 * full + 6 * band)
    # six window layers counted as full ones would read 42% more, and
    # ``step_mfu.train`` with them
    assert (6.0 * (outside + 8 * slots * expert) + 8 * full) / got \
        == pytest.approx(1.424, abs=0.002)
    assert got == pytest.approx(2.17e9, rel=5e-3)
    trained = A.train_flops_per_token(dict(cfg, train_router=True), BATCH)
    assert trained - got == pytest.approx(6.0 * 8 * h * 64 * 2 / 3)
    assert A.routed_share(cfg) == 1 / 8
    assert A.expert_shape(cfg) == dict(held=8, h=2304, i=896)


# ----------------------------------------- readers on a synthetic table
class _Models:
    def __init__(self, window=True):
        if window:
            self.window_shape = lambda cfg, batch: dict(
                b=1, h=2, kv=1, s=64, d=16, window=16)


class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 1, "seq_len": 64}}
    cfg = {}


P = "jit(train_step)/MellumForCausalLM/model/layer_2/checkpoint"
Q = "jit(train_step)/MellumForCausalLM/model/layer_3/checkpoint"
B = ("jit(train_step)/backward/MellumForCausalLM/model/layer_{}/"
     "transpose(jvp(backward))/MellumForCausalLM/model/layer_{}/jvp()/"
     "checkpoint")
OPS = [     # (event's kernel, op_name, duration in ns) of one step
    ("fusion", f"{P}/window_attention/qkv/q_proj/dot_general", 400),
    ("fusion", f"{P}/window_attention/qkv/k_proj/dot_general", 50),
    ("fusion", f"{P}/window_attention/rope/mul", 60),
    ("flash_window_fwd", f"{P}/window_attention/pallas_call", 1000),
    ("fusion", f"{P}/window_attention/o_proj/dot_general", 200),
    ("fusion", f"{P}/routed_experts/router/dot_general", 70),
    ("fusion", f"{Q}/full_attention/qkv/v_proj/dot_general", 300),
    ("fusion", f"{Q}/full_attention/rope/mul", 80),
    ("flash_attention_fwd", f"{Q}/full_attention/pallas_call", 4000),
    ("flash_window_bwd", f"{B.format(2, 2)}/window_attention/pallas_call",
     3000),
    ("fusion", f"{B.format(2, 2)}/window_attention/qkv/q_proj/transpose",
     250),
    ("flash_attention_bwd", f"{B.format(3, 3)}/full_attention/pallas_call",
     9000),
    ("fusion", f"{B.format(3, 3)}/full_attention/o_proj/transpose", 150),
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


def test_the_three_readers_on_a_synthetic_table(monkeypatch):
    run, raw = _synthetic_run(_Models())
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    got = _read(run)
    assert got["window_attention_device_ms.train"] == pytest.approx(
        (400 + 50 + 60 + 1000 + 200 + 3000 + 250) * 1e-6)
    assert got["full_attention_device_ms.train"] == pytest.approx(
        (300 + 80 + 4000 + 9000 + 150) * 1e-6)
    # the toy shape's band: 16 x 64 - 16 x 15 / 2 = 904 pairs, two
    # products forward and four backward of 2 x 2 heads x 16 flops a
    # pair; at the toy peaks both directions are bound by their bytes
    # (q, o of 2 heads and k, v of 1 forward, twice that backward, and
    # the row statistics), not by 0.12 and 0.23 us of products; one call
    # of each a step
    assert loader.module("kernel_costs", "window_attention").pairs(
        64, 16) == 904
    fwd_bytes = 2 * 16 * 64 * (2 * 2 + 2 * 1) + 4 * 2 * 64
    bwd_bytes = 2 * 16 * 64 * (4 * 2 + 4 * 1) + 4 * 2 * 64
    assert 4 * 2 * 2 * 16 * 904 / 1e12 < bwd_bytes / 1e11
    assert got["window_attention_roofline.train"] == pytest.approx(
        100 * ((fwd_bytes + bwd_bytes) / 1e11) / 4000e-9)
    assert any('"window_attention_bound": {"fwd": "bandwidth"' in n
               for n in run.notes)
    assert any('"window_attention_calls": {"fwd": 2, "bwd": 2}' in n
               for n in run.notes)
    # the accepted reader of the flash pair reads the full layers alone:
    # the window kernels' names do not hold its kernels'
    n_fwd, t_fwd = run.trace.kernel_seconds("flash_attention_fwd")
    n_bwd, t_bwd = run.trace.kernel_seconds("flash_attention_bwd")
    assert (n_fwd, n_bwd) == (2, 2)
    assert t_fwd + t_bwd == pytest.approx(2 * 13000e-9)
    # and so do the routed block's parts, LFM2's readers
    block = _read(run, L.BLOCK_PARTS)
    assert block["router_device_ms.train"] == pytest.approx(70e-6)
    assert block["expert_mlp_device_ms.train"] == pytest.approx(700e-6)


def test_the_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    """The parent commit's program on another family: no scope and no
    kernel of this family in the trace, no ``window_shape`` in the
    adapter.  Each reader returns None and raises nothing; so does each
    on an untraced run."""
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
    run, raw = _synthetic_run(_Models(window=False))
    monkeypatch.setattr(pr, "load", lambda path: raw)
    assert loader.module("metrics", "window_attention_roofline.train").read(
        run) is None


@pytest.mark.parametrize("metric", METRICS)
def test_each_new_reader_is_found_by_name_and_its_entry_is_the_cell_s(metric):
    assert callable(loader.module("metrics", metric).read)
    bench = loader.benchmark()
    entry = loader.by_name(bench["per_layer"], metric, "metric")
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "train_tokens_per_s"
    assert (entry["source"], entry["unit"], entry["better"]) == (
        ("device_trace", "%", "higher")
        if metric.endswith("roofline.train")
        else ("device_trace", "ms", "lower"))
    assert entry["layer"] == (
        "kernels: ops/pallas/" if metric.endswith("roofline.train")
        else "model step: models/mellum.py, ops/pallas/flash_attention.py")
    # new entries stand at the end of their list
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(METRICS)


@pytest.mark.parametrize("metric", SHARED + L.EVERY_STEP + L.EVERY_BLOCK)
def test_the_cell_is_appended_to_the_lists_it_shares(metric):
    bench = loader.benchmark()
    entry = loader.by_name(bench["end_to_end"] + bench["per_layer"], metric,
                           "metric")
    # the cells that were there stay first, in their order
    before = [c for c in entry["workloads"] if c != CELL]
    assert before == [c for c in ACCEPTED if c in before]
    assert entry["workloads"] == before + [CELL]


@pytest.mark.parametrize("metric", NOT_ITS)
def test_what_the_family_lacks_is_not_listed_for_it(metric):
    """No short conv, no latent, no shared expert, no linear attention."""
    entry = loader.by_name(loader.benchmark()["per_layer"], metric, "metric")
    assert CELL not in entry["workloads"]


def test_the_cell_has_its_limits_and_each_says_where_it_came_from():
    limits = loader.data("limits", CELL)
    assert set(limits) == {"loss_gap_step1", "loss_gap_step2",
                           "loss_gap_step3", "first_grad_norm_gap",
                           "first_grad_sketch_gap", "param_change_norm_gap"}
    for name, entry in limits.items():
        assert entry["limit"] > 0 and "PR 42" in entry["set_from"], name


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
    yield L.context(L.tiny("tiny-mellum"), traffic,
                    L.tiny("limits-tiny-mellum-train"), seed=2,
                    seconds=0.3)
    paddle.set_flags({"fused_opt": fused})
    take_moe()
    reg._metrics.update(gauges)
    moe._calls_of.clear()
    moe._calls_of.update(rings)


def test_the_toy_keeps_a_whole_period_and_counts_its_parameters(toy):
    cfg = toy.cfg
    table = toy.reference.table(cfg)
    assert sum(math.prod(s) for s, _, _ in table.values()) \
        == cfg["parameters"]
    assert toy.reference.plan(cfg) == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert cfg["sliding_window"] < toy.traffic["batch"]["seq_len"]


def test_train_loop_end_to_end_on_the_toy(toy):
    run = loader.module("drivers", "train_loop").run(toy)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert any('"programs_compiled_in_window": 0' in n for n in run.notes)
    assert any('"step_programs": 1' in n for n in run.notes)
    # the counters the readers use were fed by the compiled step
    tokens, shares = toy.models.expert_counters()
    steps = 3 + 2 + run.attempted
    assert sorted(tokens) == [f"layer_{i}" for i in range(4)]
    assert sum(tokens["layer_1"]) == pytest.approx(
        shares["layer_1"] * steps * 2 * 32 * 3)
    share = loader.module("metrics", "routed_here_share.train").read(run)
    assert share == pytest.approx(sum(shares.values()) / 4)
    assert 0 < share < 1


_REFERENCE = {}     # the toy's reference steps, made by the first to ask


def _checked(toy, break_it=None):
    """The program's side of the check from a program built anew by the
    adapter, ``break_it(program)`` applied between the build and the
    first step, against the reference's: the cell's ``Checks``.  (The
    same build left alone is ``test_train_loop_end_to_end_on_the_toy``,
    which is correct.)"""
    from perf import check, traffic_gen
    drv = loader.module("drivers", "train_loop")
    pool = traffic_gen.train_batches(
        toy.traffic["batch"], toy.cfg["data_vocab_size"], toy.seed, 3)
    if not _REFERENCE:
        _REFERENCE.update(drv.reference_steps(toy, pool))
    ref = _REFERENCE
    if break_it == "fp8":
        mine = drv.reference_steps(toy, pool, "fp8")
    else:
        program = toy.models.build_train(toy.cfg, toy.traffic["batch"])
        if break_it is not None:
            break_it(program)
        mine = drv.checked_steps(toy, program, pool)
    checks = check.Checks(toy.limits)
    check.train_checks(checks, mine, ref)
    return checks


def test_fp8_control_is_not_correct_on_the_toy(toy):
    control = _checked(toy, "fp8")
    assert not control.correct
    assert not control.as_dict()["first_grad_sketch_gap"]["ok"]


def test_windows_run_as_causal_are_not_correct_on_the_toy(toy):
    """The fault this configuration is likeliest to hide: the window
    layers run as plain causal ones.  Not a switch in the program: the
    adapter's own build, whose three window layers have ``window`` taken
    away before the first step."""
    def forget_the_window(program):
        layers = program.model.model.layers
        windows = [getattr(layer, layer._operator) for layer in layers
                   if layer._operator == "window_attention"]
        assert [op.window for op in windows] \
            == [toy.cfg["sliding_window"]] * 3
        for op in windows:
            op.window = None

    broken = _checked(toy, forget_the_window)
    assert not broken.correct
    failed = {n for n, row in broken.as_dict().items() if not row["ok"]}
    assert {"first_grad_norm_gap", "first_grad_sketch_gap"} <= failed
