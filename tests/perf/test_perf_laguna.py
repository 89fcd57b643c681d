"""The laguna-xs.2 configuration and its cell: the file against
BENCHMARK.json, the catalog's keys and its own arithmetic, the model's
operations a token against a hand count, the new reader on a synthetic
phase table, and the training driver end to end on a toy of the family
with its three faults.  About 95 s alone in one process (the toy's
runs: the step's first call compiles some 600 per-op programs).

Nothing here asks that this cell or its metric be the LAST entry of a
list of BENCHMARK.json: the next cell is appended after it."""
import json
import math
import os

import benchmark_history as H
import pytest
import perf_testlib as L

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

CONFIG = "laguna-xs.2"
CELL = "laguna-xs.2.pretrain_8k"
METRIC = "attention_gate_device_ms.train"
# what the cell joins: end to end, every training cell's, the sparse
# block's, and the readers of the layers it shares with other families
SHARED = ("train_tokens_per_s", "dispatch_ms.train", "input_ms.train",
          "step_device_ms.train", "device_idle_share.train",
          "flash_attention_roofline.train", "routed_here_share.train",
          "shared_expert_device_ms.train",
          "window_attention_device_ms.train",
          "full_attention_device_ms.train",
          "window_attention_roofline.train", "host_stall_share.train",
          "step_growth_share.train", "host_busy_share.train",
          "call_host_ms.train")
NOT_ITS = ("short_conv_device_ms.train", "latent_attention_device_ms.train",
           "latent_glue_device_ms.train", "linear_attention_device_ms.train",
           "kda_chunk_device_ms.train", "kda_glue_device_ms.train",
           "kda_chunk_roofline.train")
BATCH = {"rows": 1, "seq_len": 8192}
BEFORE = ["gpt2-medium.pretrain", *L.SPARSE_CELLS,
          "mellum2-12b-a2.5b.pretrain_8k"]
BAND = 512 * 8192 - 512 * 511 // 2      # 4,063,488 pairs in a 512-key band


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(L.ROOT, "perf", "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def _adapter():
    return loader.module("models", "laguna")


def test_parameters_from_the_table(cfg):
    R = loader.module("reference", "laguna")
    table = R.table(cfg)
    count = sum(math.prod(shape) for shape, _, _ in table.values())
    assert count == cfg["parameters"] == R.parameters(cfg) == 691_623_936
    layers = {}
    for name, (shape, _, _) in table.items():
        if name.startswith("layers."):
            i, leaf = name.split(".", 2)[1:]
            layers.setdefault(int(i), {})[leaf] = math.prod(shape)

    def attention(i):
        return sum(v for k, v in layers[i].items() if k.startswith("attn."))

    # q and o by the layer's heads, k and v by 8, the gate hidden x heads
    assert attention(0) == attention(4) == 29_360_128 + 98_304
    assert attention(1) == attention(2) == attention(3) \
        == 37_748_736 + 131_072
    assert layers[0]["attn.g"] == 2048 * 48 and layers[1]["attn.g"] == 2048 * 64
    assert sum(v for k, v in layers[0].items() if k.startswith("mlp.")) \
        == 3 * 2048 * 8192 == 50_331_648
    for i in (1, 2, 3, 4):
        assert sum(v for k, v in layers[i].items()
                   if k.startswith("moe.w")) == 32 * 3_145_728
        assert sum(v for k, v in layers[i].items()
                   if k.startswith("shared.")) == 3_145_728
        assert layers[i]["moe.router"] == 2048 * 256
    assert [sum(layers[i].values()) for i in range(5)] == [
        79_794_176, 142_217_216, 142_217_216, 142_217_216, 133_795_840]
    assert math.prod(table["embed"][0]) == math.prod(table["head"][0]) \
        == 12544 * 2048
    # this repo's step state, 14 bytes a parameter (PERF.md section 7):
    # 57% of the chip's 16.91 GB
    assert round(14 * count / 1e9, 2) == 9.68


def test_the_file_against_the_benchmark_and_the_published_widths(cfg):
    bench = loader.benchmark()
    entry = loader.by_name(bench["configs"], CONFIG, "config")
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    assert entry["file"] == f"perf/configs/{CONFIG}.json"
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert set(cfg["changed"]) == set(cfg["reduced"])
    assert "8 chips share each layer" in cfg["deployment"]
    cell = loader.by_name(bench["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain_lm_1x8192", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # after the cells and configurations that were there
    names = [w["name"] for w in bench["workloads"]]
    assert names[:len(BEFORE)] == BEFORE and names.index(CELL) == len(BEFORE)
    # every width as published; only depth, experts held and vocabulary
    # rows are this chip's share
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"],
            cfg["rms_norm_eps"]) == (2048, 48, 8, 128, 512, 8192, 512, 512,
                                     8, 2.5, 1e-6)
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["data_vocab_size"]) == (5, 32, 12544, 12544)
    # within the guide's floors: the dense layer and a whole period
    # after it, four sparse layers, 32 experts, an eighth of the
    # vocabulary
    assert loader.module("reference", "laguna").plan(cfg) == [
        ("full_attention", "dense", 48), ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64), ("full_attention", "sparse", 48)]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["num_experts"] * 8 == cfg["published"]["num_experts"]
    for key in ("gating", "router", "qk_norm", "aux_loss", "initializer",
                "rope", "sliding_window", "expert_load", "recompute_policy",
                "expert_slots_at_a_time"):
        assert key in cfg["assumed"], key
    # the lone share's choices; the even spread's load (8,192 slots a
    # layer) is the END of the block's own chunk of 8,192, so the
    # chunk is 16,384 as Mellum2's
    assert (cfg["train_router"], cfg["expert_slots_at_a_time"]) \
        == (False, 16384)
    even = BATCH["seq_len"] * cfg["num_experts_per_tok"] \
        * cfg["num_experts"] // cfg["published"]["num_experts"]
    assert even == 8192
    assert loader.module("reference", "laguna").table(cfg)["embed"][2] == 1.0
    prec = cfg["precision"]["train"]
    other = loader.data("configs", "moonlight-16b-a3b")["precision"]["train"]
    assert prec == {**other, "router": prec["router"]}


def test_the_catalog_keys_are_all_there_and_only_the_reduced_differ(cfg):
    """Every number of the published config under its own key, the
    nested group and the three per-layer lists whole."""
    period = ["full_attention"] + ["sliding_attention"] * 3
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 262144,
        "attention_bias": False, "rms_norm_eps": 1e-06, "num_experts": 256,
        "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1,
                "beta_fast": 64, "attention_factor": 1.4158883083359672,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000,
                                  "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096},
        "layer_types": period * 10,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10}
    differ = {k for k, v in published.items() if cfg[k] != v}
    assert differ == set(cfg["reduced"])
    assert {k: published[k] for k in differ} == cfg["published"]


def test_kernel_shapes_by_layer_type(cfg):
    A = _adapter()
    assert A.attention_shape(cfg, BATCH) == dict(
        b=1, h=48, sq=8192, sk=8192, d=128, causal=True)
    assert A.window_shape(cfg, BATCH) == dict(
        b=1, h=64, kv=8, s=8192, d=128, window=512)
    assert A.expert_shape(cfg) == dict(held=32, h=2048, i=512)
    assert A.routed_share(cfg) == 1 / 8
    cost = loader.module("kernel_costs", "window_attention")
    assert cost.pairs(8192, 512) == BAND == 4_063_488
    # 0.121 of a causal layer's pairs, and all of it products on a v5e
    assert BAND / (8192 * 8192 / 2) == pytest.approx(0.1211, rel=1e-3)
    from perf import readers
    peaks = loader.peaks("TPU v5 lite")
    for need in (cost.fwd, cost.bwd):
        assert readers.least_seconds(
            *need(**A.window_shape(cfg, BATCH)), peaks)[1] == "compute"
    # layers of one type with two head counts are no one call's shape
    lists = dict(cfg, num_attention_heads_per_layer=[48, 64, 64, 32] * 10)
    with pytest.raises(ValueError, match="sliding_attention layers"):
        A.window_shape(lists, BATCH)


def test_model_flops_against_a_hand_count(cfg):
    A = _adapter()
    got = A.train_flops_per_token(cfg, BATCH)
    h = 2048
    # the router's product forward only: ``train_router`` is false
    assert cfg["train_router"] is False

    def attention(heads):       # q and o, k and v, the gate's projection
        return 2 * h * heads * 128 + 2 * h * 8 * 128 + h * heads

    sparse = h * 256 / 3 + 3 * h * 512 + 8 * (32 / 256) * 3 * h * 512
    outside = (12544 * h + 2 * attention(48) + 3 * attention(64)
               + 3 * h * 8192 + 4 * sparse)
    full = 6 * 2 * 48 * 128 * (8192 * 8192 / 2) / 8192
    band = 6 * 2 * 64 * 128 * BAND / 8192
    assert got == pytest.approx(6.0 * outside + 2 * full + 3 * band)
    # attention's own products: 302 MFLOP a token a full layer, 49 a
    # window layer, 31% of the model's 2.40 GFLOP a token
    assert full == pytest.approx(302.0e6, rel=1e-3)
    assert band == pytest.approx(48.8e6, rel=2e-3)
    assert got == pytest.approx(2.397e9, rel=1e-3)
    assert (2 * full + 3 * band) / got == pytest.approx(0.313, abs=0.002)
    # window layers counted as full ones at their 64 heads would read
    # 45% more, and ``step_mfu.train`` with them
    as_full = 6 * 2 * 64 * 128 * (8192 * 8192 / 2) / 8192
    assert (got + 3 * (as_full - band)) / got == pytest.approx(1.443,
                                                               abs=0.002)
    trained = A.train_flops_per_token(dict(cfg, train_router=True), BATCH)
    assert trained - got == pytest.approx(6.0 * 4 * h * 256 * 2 / 3)


# ------------------------------------------------- the new reader
class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 1, "seq_len": 64}}
    cfg = {}
    models = object()


def _run_of(raw):
    run = common.Run(_Ctx())
    run.trace = tr.Trace({"planes": [
        {"name": p["name"], "lines": [{"name": ln["name"],
                                       "events": ln["events"]}
                                      for ln in p["lines"]]}
        for p in raw["planes"]]})
    return run


P = "jit(train_step)/LagunaForCausalLM/model/layer_{}/checkpoint"
R_ = ("jit(train_step)/backward/LagunaForCausalLM/model/layer_{0}/"
      "checkpoint/rematted_computation")
B = ("jit(train_step)/backward/LagunaForCausalLM/model/layer_{0}/"
     "transpose(jvp(backward))/LagunaForCausalLM/model/layer_{0}/jvp()/"
     "checkpoint")
OPS = [     # (event's kernel, op_name, duration in ns) of one step
    ("fusion", f"{P.format(1)}/window_attention/qkv/q_proj/dot_general", 400),
    ("flash_window_fwd", f"{P.format(1)}/window_attention/pallas_call", 1000),
    ("fusion", f"{P.format(1)}/window_attention/out_gate/g_proj/dot_general",
     30),
    ("fusion", f"{P.format(1)}/window_attention/out_gate/mul", 90),
    ("fusion", f"{P.format(1)}/window_attention/o_proj/dot_general", 200),
    ("flash_attention_fwd", f"{P.format(4)}/full_attention/pallas_call",
     4000),
    ("fusion", f"{P.format(4)}/full_attention/out_gate/mul", 70),
    ("fusion", f"{R_.format(4)}/full_attention/out_gate/logistic", 20),
    ("fusion", f"{B.format(4)}/full_attention/out_gate/mul", 150),
    ("fusion", f"{B.format(4)}/full_attention/out_gate/g_proj/transpose", 60),
    ("flash_attention_bwd", f"{B.format(4)}/full_attention/pallas_call",
     9000),
    ("fusion", f"{R_.format(1)}/window_attention/out_gate/logistic", 25),
    ("fusion", f"{B.format(1)}/window_attention/out_gate/reduce_sum", 110),
    ("flash_window_bwd", f"{B.format(1)}/window_attention/pallas_call", 3000),
    ("fusion", f"{B.format(1)}/shared_expert/up_proj/transpose", 45),
    ("fusion", "jit(train_step)/optimizer/mul", 40),
]


def _synthetic(ops=OPS):
    events, names, at = [], [], 1000
    for _ in range(2):                          # two steps traced
        for i, (kernel, op, ns) in enumerate(ops):
            events.append([f"%{kernel}.{i} f32[8]", at, ns])
            names.append(op)
            at += ns + 10
    host = [["train_step", 900, at], ["to_static.call", 950, 100],
            ["to_static.call", 5000, 100]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": events, "op_names": names}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}


def test_the_new_reader_on_a_synthetic_table(monkeypatch):
    """Operations named as the compiled step names them
    (``tests/test_laguna.py`` reads these scope paths in the program's
    HLO): the reader takes every phase's operations under ``out_gate``
    of either attention, and nothing else's."""
    raw = _synthetic()
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    run = _run_of(raw)
    got = loader.module("metrics", METRIC).read(run)
    gate = 30 + 90 + 70 + 20 + 150 + 60 + 25 + 110
    assert got == pytest.approx(gate * 1e-6)
    # inside the two attention readers' time, which hold the kernels too
    window = loader.module("metrics",
                           "window_attention_device_ms.train").read(run)
    full = loader.module("metrics", "full_attention_device_ms.train").read(run)
    assert window == pytest.approx(
        (400 + 1000 + 30 + 90 + 200 + 25 + 110 + 3000) * 1e-6)
    assert full == pytest.approx((4000 + 70 + 20 + 150 + 60 + 9000) * 1e-6)
    assert loader.module("metrics", "shared_expert_device_ms.train").read(
        run) == pytest.approx(45e-6)
    # every phase's: forward, the recomputed sigmoid, backward
    phases = {pr.phase_of_op(op) for _, op, _ in OPS if "/out_gate/" in op}
    assert phases == {"forward", "recompute", "backward"}


def test_the_new_reader_on_a_recorded_trace(monkeypatch):
    """Two steady steps of the cell's traced window on the v5e, cut to
    the operations under ``out_gate`` and the four flash kernels, with
    their op_names as the chip's compiler left them
    (``tests/perf/data/recorded_laguna_gate_trace.json``; its ``source``
    says how it was cut): the gate's device time a step."""
    rec = L.tiny("recorded_laguna_gate_trace")
    raw, want = rec["raw"], rec["expect"]
    monkeypatch.setattr(tr, "find_xplane", lambda d: "recorded")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    run = _run_of(raw)
    got = loader.module("metrics", METRIC).read(run)
    assert got == pytest.approx(want["out_gate_ns"] / want["calls"] * 1e-6)
    assert got == pytest.approx(6.02, abs=0.01)     # ms a step
    ops = raw["planes"][0]["lines"][0]["op_names"]
    assert sum("/out_gate/" in op for op in ops) == want["out_gate_events"]
    # the projection and the product in the forward, the sigmoid run
    # again in the backward's recompute, and their transposes
    assert {pr.phase_of_op(op) for op in ops if "/out_gate/" in op} \
        == {"forward", "recompute", "backward"}
    for kind in ("window_attention", "full_attention"):
        assert any(f"/{kind}/out_gate/" in op for op in ops), kind
    # the kernels beside it are the two attention readers', not its
    both = sum(loader.module("metrics", f"{k}_device_ms.train").read(run)
               for k in ("window_attention", "full_attention"))
    assert both == pytest.approx(
        (want["out_gate_ns"] + want["kernel_ns"]) / want["calls"] * 1e-6)


def test_the_reader_finds_nothing_on_a_program_without_the_scope(
        monkeypatch):
    """The parent commit's program: no ``out_gate`` in the trace.  The
    reader returns None and raises nothing; so it does untraced."""
    events = [["%fusion.1 f32[8]", 1000, 100],
              ["%flash_attention_bwd.2 f32[8]", 1200, 300]]
    names = ["jit(train_step)/backward/GPTForCausalLM/gpt/block_1/mul",
             "jit(train_step)/backward/GPTForCausalLM/gpt/block_1/attn/"
             "pallas_call"]
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": events, "op_names": names}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["train_step", 900, 2000], ["to_static.call", 950, 100]]}]}]}
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    assert loader.module("metrics", METRIC).read(_run_of(raw)) is None
    assert loader.module("metrics", METRIC).read(common.Run(_Ctx())) is None


def test_the_new_reader_is_found_by_name_and_its_entry_is_the_cells():
    assert callable(loader.module("metrics", METRIC).read)
    bench = loader.benchmark()
    entry = loader.by_name(bench["per_layer"], METRIC, "metric")
    assert CELL in entry["workloads"]
    assert entry["moves"] == "train_tokens_per_s"
    assert (entry["source"], entry["unit"], entry["better"]) == (
        "device_trace", "ms", "lower")
    assert entry["layer"] == (
        "model step: models/laguna.py, ops/pallas/flash_attention.py")
    # after the metrics that were there
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index(METRIC) > names.index(
        "window_attention_roofline.train")


def test_the_benchmark_is_the_one_before_plus_this_cell():
    """The benchmark as this PR left it (``as_of(CELL)``: this cell is
    its last whatever comes later) is the one ``test_perf_mellum.py`` is
    shown (``conftest.py``) plus one configuration, one cell, one metric
    and the cell's name at the end of the lists it joins: nothing that
    was there is edited, moved or taken away."""
    before, after = H.as_of(BEFORE[-1]), H.as_of(CELL)
    assert set(after) == set(before) == set(H.whole())
    for key in set(after) - {"configs", "workloads", "end_to_end",
                             "per_layer"}:
        assert after[key] == before[key] == H.whole()[key], key
    assert after["workloads"][:-1] == before["workloads"]
    assert after["workloads"][-1]["name"] == CELL
    assert after["configs"][:-1] == before["configs"]
    assert after["configs"][-1]["name"] == CONFIG
    assert after["per_layer"][-1]["name"] == METRIC
    assert after["per_layer"][-1]["workloads"] == [CELL]
    joined = []
    for key, rows in (("end_to_end", after["end_to_end"]),
                      ("per_layer", after["per_layer"][:-1])):
        assert len(rows) == len(before[key])
        for now, then in zip(rows, before[key]):
            if now != then:
                assert now == {**then, "workloads": then["workloads"]
                               + [CELL]}, now["name"]
                joined.append(now["name"])
    assert sorted(joined) == sorted(SHARED + L.EVERY_STEP + L.EVERY_BLOCK)


@pytest.mark.parametrize("metric", SHARED + L.EVERY_STEP + L.EVERY_BLOCK)
def test_the_cell_is_appended_to_the_lists_it_shares(metric):
    bench = loader.benchmark()
    entry = loader.by_name(bench["end_to_end"] + bench["per_layer"], metric,
                           "metric")
    listed = entry["workloads"]
    # the cells that were there stay first, in their order
    before = listed[:listed.index(CELL)]
    assert before == [c for c in BEFORE if c in before] and before


@pytest.mark.parametrize("metric", NOT_ITS)
def test_what_the_family_lacks_is_not_listed_for_it(metric):
    """No short conv, no latent attention, no linear attention."""
    entry = loader.by_name(loader.benchmark()["per_layer"], metric, "metric")
    assert CELL not in entry["workloads"]


def test_the_cell_has_its_limits_and_each_says_where_it_came_from():
    limits = loader.data("limits", CELL)
    assert set(limits) == {"loss_gap_step1", "loss_gap_step2",
                           "loss_gap_step3", "first_grad_norm_gap",
                           "first_grad_sketch_gap", "param_change_norm_gap"}
    for name, entry in limits.items():
        assert entry["limit"] > 0 and "PR 44" in entry["set_from"], name


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

    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from perf.reference import common as C
    gauges, rings = take_moe(), dict(moe._calls_of)
    # the seeded leaves are made once per (table, seed, type): every
    # check makes them four times, each a jitted program of one normal
    # a leaf that the CPU compiles for seconds.  Fresh arrays a call:
    # the reference donates them
    made, plain, patch = {}, C.make_weights, pytest.MonkeyPatch()

    def make_weights(table, seed, low_dtype=None):
        key = (tuple(sorted(table)), seed, low_dtype)
        if key not in made:
            made[key] = jax.device_get(plain(table, seed, low_dtype))
        return {k: jnp.asarray(v) for k, v in made[key].items()}

    patch.setattr(C, "make_weights", make_weights)
    moe._calls_of.clear()
    # the program's flag the adapter sets from the configuration
    fused = paddle.get_flags("fused_opt")["fused_opt"]
    traffic = loader.data("traffic", "pretrain_lm_1x8192")
    traffic["batch"].update(rows=2, seq_len=32)
    traffic["distinct_batches"] = 6
    yield L.context(L.tiny("tiny-laguna"), traffic,
                    L.tiny("limits-tiny-laguna-train"), seed=1,
                    seconds=0.3)
    patch.undo()
    paddle.set_flags({"fused_opt": fused})
    take_moe()
    reg._metrics.update(gauges)
    moe._calls_of.clear()
    moe._calls_of.update(rings)


def test_the_toy_has_every_operator_and_counts_its_parameters(toy):
    cfg = toy.cfg
    table = toy.reference.table(cfg)
    assert sum(math.prod(s) for s, _, _ in table.values()) \
        == cfg["parameters"]
    # a full layer (over the dense MLP) and window layers (over the
    # shared and the routed experts, two of them: the readers sum over
    # layers); a full layer over experts is tests/test_laguna.py's
    assert [(kind, ffn) for kind, ffn, _ in toy.reference.plan(cfg)] == [
        ("full_attention", "dense")] + [("sliding_attention", "sparse")] * 2
    assert cfg["sliding_window"] < toy.traffic["batch"]["seq_len"]
    assert cfg["rope_parameters"]["full_attention"][
        "partial_rotary_factor"] == 0.5


def test_train_loop_end_to_end_on_the_toy(toy):
    run = loader.module("drivers", "train_loop").run(toy)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert any('"programs_compiled_in_window": 0' in n for n in run.notes)
    assert any('"step_programs": 1' in n for n in run.notes)
    # the counters the readers use were fed by the compiled step
    tokens, shares = toy.models.expert_counters()
    steps = 3 + 2 + run.attempted
    assert sorted(tokens) == ["layer_1", "layer_2"]
    assert sum(tokens["layer_1"]) == pytest.approx(
        shares["layer_1"] * steps * 2 * 32 * 3)
    share = loader.module("metrics", "routed_here_share.train").read(run)
    assert share == pytest.approx(sum(shares.values()) / 2)
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
        break_it(program)
        mine = drv.checked_steps(toy, program, pool)
    checks = check.Checks(toy.limits)
    check.train_checks(checks, mine, ref)
    return checks


def _attention_ops(program):
    return [getattr(layer, layer._operator)
            for layer in program.model.model.layers]


def _failed(checks):
    assert not checks.correct
    return {n for n, row in checks.as_dict().items() if not row["ok"]}


def test_fp8_control_is_not_correct_on_the_toy(toy):
    assert "first_grad_sketch_gap" in _failed(_checked(toy, "fp8"))


def test_full_layers_that_rotate_the_whole_head_are_not_correct_on_the_toy(
        toy):
    """The fault this configuration is likeliest to hide: a reader of
    the config who misses ``partial_rotary_factor`` rotates all of a
    full layer's head (and blends YaRN's frequencies over all its
    pairs).  Not a switch in the program: the adapter's own build, whose
    two full layers are given tables made at a factor of 1 before the
    first step."""
    import copy

    from paddle_tpu.models.mellum import RopeTables

    def rotate_whole_heads(program):
        full = [op for op in _attention_ops(program)
                if op.kind == "full_attention"]
        assert [op._tables.width(op.kind) for op in full] == [4]
        for op in full:
            whole = copy.copy(op._tables.cfg)
            whole.rope_parameters = {
                k: {**v, "partial_rotary_factor": 1}
                for k, v in whole.rope_parameters.items()}
            op._tables = RopeTables(whole)

    assert {"first_grad_norm_gap", "first_grad_sketch_gap"} \
        <= _failed(_checked(toy, rotate_whole_heads))


def test_a_gate_left_out_is_not_correct_on_the_toy(toy):
    """Every layer's gate reads one: its projection's result is
    replaced by 30 before the sigmoid (one, in bfloat16), in the
    adapter's own build."""
    def leave_the_gate_out(program):
        ops = _attention_ops(program)
        assert len(ops) == 3
        for op in ops:
            lin = op.g_proj
            plain = type(lin).forward
            lin.forward = (lambda x, lin=lin, plain=plain:
                           plain(lin, x) * 0 + 30.0)

    assert {"first_grad_norm_gap", "first_grad_sketch_gap",
            "param_change_norm_gap"} \
        <= _failed(_checked(toy, leave_the_gate_out))
