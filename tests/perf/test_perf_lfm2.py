"""The lfm2-24b-a2b configuration and its cell: the file against
BENCHMARK.json and its own arithmetic, the expert products' cost, the
six new readers on a synthetic phase table, and the training driver
end to end on a toy of the family."""
import json
import math
import os

import pytest
import perf_testlib as L

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

CELL = "lfm2-24b-a2b.pretrain_8k"
METRICS = ("router_device_ms.train", "expert_dispatch_device_ms.train",
           "expert_mlp_device_ms.train", "short_conv_device_ms.train",
           "expert_mlp_roofline.train", "expert_load_max_over_mean.train")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(L.ROOT, "perf", "configs",
                           "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def _adapter():
    return loader.module("models", "lfm2_moe")


def test_parameters_from_the_table(cfg):
    table = loader.module("reference", "lfm2_moe").table(cfg)
    count = sum(math.prod(shape) for shape, _, _ in table.values())
    assert count == cfg["parameters"] == 469_284_992
    experts = sum(math.prod(s) for k, (s, _, _) in table.items()
                  if ".moe.w" in k)
    assert experts == 4 * 8 * 3 * 2048 * 1536        # 302M of the 469M


def test_the_file_against_the_benchmark_and_the_published_widths(cfg):
    bench = loader.benchmark()
    entry = loader.by_name(bench["configs"], "lfm2-24b-a2b", "config")
    assert entry["source"] == cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert "8 chips share each layer" in cfg["deployment"]
    cell = loader.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["chips"]) == ("lfm2-24b-a2b", 1)
    # every width as published; only depth, experts held and vocabulary
    # rows are this chip's share
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["conv_L_cache"]) == (2048, 11776, 1536, 4, 32, 8, 3)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_dense_layers": 2, "num_experts": 64,
                                "vocab_size": 65536}
    assert len(cfg["layer_types"]) == 40        # the published list, whole
    plan = loader.module("reference", "lfm2_moe").plan(cfg)
    assert plan == [("conv", "dense"), ("full_attention", "sparse"),
                    ("conv", "sparse"), ("conv", "sparse"),
                    ("conv", "sparse")]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 8192)
    for key in ("router_scores", "expert_bias", "norm_topk_prob",
                "tie_word_embeddings", "qk_norm", "initializer",
                "expert_bias_seed"):
        assert key in cfg["assumed"]


def test_expert_products_cost_on_a_hand_counted_case():
    cost = loader.module("kernel_costs", "expert_mlp")
    # 10 slots, 2 experts, 4 -> 3 -> 4: each forward product is
    # 2 * 10 * 4 * 3 = 240 operations, three of them; the backward six
    flops, nbytes = cost.fwd(slots=10, held=2, h=4, i=3)
    assert flops == 720
    assert nbytes == 2 * (10 * 4 + 10 * 4 + 3 * 2 * 4 * 3)
    flops, nbytes = cost.bwd(slots=10, held=2, h=4, i=3)
    assert flops == 1440
    assert nbytes == 2 * (3 * 10 * 4 + 6 * 2 * 4 * 3)


def test_model_flops_count_expert_work_by_the_routed_share(cfg):
    A = _adapter()
    batch = {"rows": 2, "seq_len": 8192}
    got = A.train_flops_per_token(cfg, batch)
    h, expert = 2048, 3 * 2048 * 1536
    outside = (8192 * h                         # the tied head
               + 4 * (4 * h * h)                # four conv operators
               + 2 * h * h + 2 * h * 512        # one attention
               + 3 * h * 11776                  # the dense MLP
               + 4 * h * 64)                    # four routers
    slots = 4 * 4 * (8 / 64)                    # 4 layers x top-4 x 1/8
    assert got == pytest.approx(
        6.0 * (outside + slots * expert) + 12 * h * 8192)
    # not the 8 held experts' weights, nor the router's 64
    assert got < 6.0 * (outside + 4 * 8 * expert)
    assert A.attention_shape(cfg, batch) == dict(
        b=2, h=32, sq=8192, sk=8192, d=64, causal=True)


# ----------------------------------------- readers on a synthetic table
class _Models:
    def __init__(self, tokens, shares, calls, family="lfm2_moe"):
        self.expert_counters = lambda: (tokens, shares)
        self.expert_calls = lambda: calls
        # the held experts' shapes as the family's adapter reads them
        # from the configuration's own spelling
        self.expert_shape = loader.module("models", family).expert_shape


class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 1, "seq_len": 100}}
    cfg = {"num_experts_per_tok": 4, "num_experts": 2, "hidden_size": 8,
           "moe_intermediate_size": 4}


P = "jit(train_step)/Lfm2MoeForCausalLM/lfm2/layer_2"
B = ("jit(train_step)/backward/Lfm2MoeForCausalLM/lfm2/layer_2/"
     "transpose(jvp(backward))/Lfm2MoeForCausalLM/lfm2/layer_2/jvp()/"
     "checkpoint")
OPS = [     # (op_name, duration in ns) of one step, two steps traced
    (f"{P}/checkpoint/feed_forward/router/dot_general", 100),
    (f"{P}/checkpoint/feed_forward/dispatch/sort", 200),
    (f"{P}/checkpoint/feed_forward/expert_mlp/while/body/checkpoint/"
     "ragged_dot_general", 1000),
    (f"{P}/checkpoint/feed_forward/combine/gather", 300),
    (f"{P}/checkpoint/conv/short_conv/mul", 50),
    (f"{P}/checkpoint/conv/in_proj/dot_general", 700),
    (f"{B}/rematted_computation/feed_forward/expert_mlp/while/body/"
     "checkpoint/rematted_computation/ragged_dot_general", 1000),
    (f"{B}/feed_forward/expert_mlp/while/body/ragged_dot_general", 2000),
    (f"{B}/feed_forward/router/transpose", 150),
    (f"{B}/feed_forward/dispatch/gather", 250),
    (f"{B}/conv/short_conv/mul", 75),
    ("jit(train_step)/optimizer/mul", 40),
    # the compiler's grouped-product kernel: its op_name is its own
    ("ragged-dot-none", 500),
]


def _synthetic_run(models, cfg=None):
    events, names, at = [], [], 1000
    for _ in range(2):
        for i, (op, ns) in enumerate(OPS):
            kernel = "ragged-dot-none" if op == "ragged-dot-none" \
                else "fusion"
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
    if cfg is not None:
        ctx.cfg = cfg
    run = common.Run(ctx)
    run.trace = tr.Trace({"planes": [
        {"name": p["name"], "lines": [{"name": ln["name"],
                                       "events": ln["events"]}
                                      for ln in p["lines"]]}
        for p in raw["planes"]]})
    return run, raw


def test_the_six_readers_on_a_synthetic_table(monkeypatch):
    tokens = {"layer_2": [300, 100]}            # slots since the build
    # seven calls counted, the window the last four, the trace its first
    # two: calls 4 and 5, 120 + 80 slots; later calls routed more
    calls = {"layer_2": {n: [here // 2, here // 2, 400] for n, here in
                         enumerate([60, 60, 60, 120, 80, 300, 340], 1)}}
    run, raw = _synthetic_run(_Models(tokens, {"layer_2": 0.25}, calls))
    run.counters = {"steps": 4}
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    got = {m: loader.module("metrics", m).read(run) for m in METRICS}
    assert got["router_device_ms.train"] == pytest.approx(250e-6)
    assert got["expert_dispatch_device_ms.train"] == pytest.approx(750e-6)
    assert got["expert_mlp_device_ms.train"] == pytest.approx(4500e-6)
    assert got["short_conv_device_ms.train"] == pytest.approx(125e-6)
    assert got["expert_load_max_over_mean.train"] == pytest.approx(1.5)
    # (120 + 80) / 2 = 100 slots a traced step: 3 x 2*100*8*4
    # operations forward and twice that backward at 1e12 a second,
    # against 14.4e3 / 25.2e3 bytes at 1e11: compute 57.6 ns
    cost = loader.module("kernel_costs", "expert_mlp")
    least = sum(max(f / 1e12, b / 1e11) for f, b in
                (cost.fwd(100, 2, 8, 4), cost.bwd(100, 2, 8, 4)))
    assert got["expert_mlp_roofline.train"] == pytest.approx(
        100 * least / 4500e-9)
    assert 0 < got["expert_mlp_roofline.train"] < 100
    # a ring that no longer holds the traced calls gives nothing
    del calls["layer_2"][4]
    assert loader.module("metrics", "expert_mlp_roofline.train").read(
        run) is None


@pytest.mark.parametrize("family, cfg", [
    ("lfm2_moe", _Ctx.cfg),
    ("kimi_linear", {"num_experts_per_token": 4, "num_experts": 2,
                     "hidden_size": 8, "moe_intermediate_size": 4}),
    ("deepseek_v3", {"num_experts_per_tok": 4, "n_routed_experts": 2,
                     "hidden_size": 8, "moe_intermediate_size": 4}),
])
def test_the_roofline_reads_the_held_experts_in_each_family_s_spelling(
        family, cfg, monkeypatch):
    """Moonlight's file spells the held experts ``n_routed_experts``,
    LFM2's and Kimi-Linear's ``num_experts``: the reader asks the
    cell's adapter, and the same slots, widths and device time give
    the same share under every spelling."""
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    read = loader.module("metrics", "expert_mlp_roofline.train").read

    def run_of(family, cfg):
        calls = {"layer_2": {n: [50, 50, 400] for n in range(1, 5)}}
        run, raw = _synthetic_run(_Models(
            {"layer_2": [300, 100]}, {"layer_2": 0.25}, calls, family), cfg)
        run.counters = {"steps": 4}
        monkeypatch.setattr(pr, "load", lambda path: raw)
        return run

    run = run_of(family, cfg)
    cost = loader.module("kernel_costs", "expert_mlp")
    least = sum(max(f / 1e12, b / 1e11) for f, b in
                (cost.fwd(100, 2, 8, 4), cost.bwd(100, 2, 8, 4)))
    got = read(run)
    assert got == pytest.approx(100 * least / 4500e-9)
    assert got == read(run_of("lfm2_moe", _Ctx.cfg))     # to the last digit
    # an adapter that cannot say what it holds gives nothing
    del run.ctx.models.expert_shape
    assert read(run) is None


def test_the_readers_find_nothing_on_a_program_without_the_scopes(
        monkeypatch):
    """The parent commit's program: no expert scope in the trace, no
    counter in the adapter.  Each reader returns None and raises
    nothing."""
    run, raw = _synthetic_run(object())
    for line in raw["planes"][0]["lines"]:
        line["events"] = [[name.replace("ragged-dot-none", "fusion"), s, d]
                          for name, s, d in line["events"]]
        line["op_names"] = [
            "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/mul"
        ] * len(line["events"])
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    for m in METRICS:
        assert loader.module("metrics", m).read(run) is None, m
    untraced = common.Run(_Ctx())
    untraced.ctx.models = object()
    for m in METRICS:
        assert loader.module("metrics", m).read(untraced) is None, m


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_has_an_entry_for_the_cells_it_reads(metric):
    """``SparseMoEBlock`` is one block for three cells: its metrics list
    the cell they were written for first and the other sparse cells
    after it (a later cell may follow); the short conv is LFM2's."""
    entry = loader.by_name(loader.benchmark()["per_layer"], metric, "metric")
    assert entry["workloads"][0] == CELL
    if metric.startswith("short_conv"):
        assert entry["workloads"] == [CELL]
    else:
        assert set(L.SPARSE_CELLS) <= set(entry["workloads"])
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == ("program_counter" if "load" in metric
                               else "device_trace")


# ------------------------------------------------ the driver, on a toy
@pytest.fixture(scope="module")
def toy():
    traffic = loader.data("traffic", "pretrain_lm_2x8192")
    traffic["batch"].update(rows=2, seq_len=32)
    traffic["distinct_batches"] = 6
    return L.context(L.tiny("tiny-lfm2_moe"), traffic,
                     L.tiny("limits-tiny-lfm2_moe-train"), seed=1,
                     seconds=0.3)


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
            shares[layer] * steps * 2 * 32 * 2)
    # and call by call: every step of the run is still in the ring
    for layer, calls in toy.models.expert_calls().items():
        assert sorted(calls) == list(range(1, steps + 1))
        assert [sum(c) for c in zip(*calls.values())][:-1] == tokens[layer]
    read = loader.module("metrics", "expert_load_max_over_mean.train").read
    assert read(run) >= 1.0


def test_fp8_control_is_not_correct_on_the_toy(toy):
    from perf import check, traffic_gen
    drv = loader.module("drivers", "train_loop")
    pool = traffic_gen.train_batches(
        toy.traffic["batch"], toy.cfg["data_vocab_size"], toy.seed, 3)
    ref = drv.reference_steps(toy, pool)
    control = check.Checks(toy.limits)
    check.train_checks(control, drv.reference_steps(toy, pool, "fp8"), ref)
    assert not control.correct
