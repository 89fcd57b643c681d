"""The benchmark's arithmetic: traffic from a seed, percentiles,
lateness, kernel costs against hand-worked cases, the trace reducer on
a small recorded trace."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

from perf import check, loader, stats, traffic_gen  # noqa: E402
from perf import trace_reduce as tr  # noqa: E402

BIG_SEED = 2**31 + 12345


# ---------------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", ["chat", "offline"])
def test_requests_are_the_seeds_and_keep_to_the_clips(mix):
    spec = loader.data("traffic", mix)["requests"]
    a = traffic_gen.requests(spec, 50257, BIG_SEED, 30)
    b = traffic_gen.requests(spec, 50257, BIG_SEED, 30)
    c = traffic_gen.requests(spec, 50257, BIG_SEED + 1, 30)
    assert len(a) == len(b) == len(c)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])
    assert any(not np.array_equal(x["prompt"], z["prompt"])
               for x, z in zip(a, c))
    # another seed offers the same sizes in another order
    for key in (lambda r: r["prompt"].size, lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
    p, o = spec["prompt_len"], spec["output_len"]
    for r in a:
        assert p["lo"] <= r["prompt"].size <= p["hi"]
        assert o["lo"] <= r["max_new"] <= o["hi"]
        assert r["prompt"].size + r["max_new"] <= spec["max_total"]
        assert r["prompt"].max() < 50257 and r["prompt"].dtype == np.int32


def test_open_loop_arrivals():
    spec = dict(loader.data("traffic", "chat")["requests"], rate=5.0)
    reqs = traffic_gen.requests(spec, 100, 7, 20)
    due = [r["due"] for r in reqs]
    assert len(reqs) == 100 and due == sorted(due)
    assert 0 <= due[0] and due[-1] < 20
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert abs(gaps.mean() - 0.2) < 0.01
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_length_quantiles_have_the_median_and_the_tail():
    spec = {"median": 192, "sigma": 0.8, "lo": 16, "hi": 768}
    q = traffic_gen.lognormal_quantiles(spec, 1001)
    assert q[500] == 192 and q.min() >= 16 and q.max() == 768
    assert 180 < np.median(q) < 200 and q.mean() > 230


@pytest.mark.parametrize("mix", ["pretrain_lm_8x1024", "pretrain_mlm_16x512"])
def test_training_batches(mix):
    spec = loader.data("traffic", mix)["batch"]
    a = traffic_gen.train_batches(spec, 30000, BIG_SEED, 3)
    b = traffic_gen.train_batches(spec, 30000, BIG_SEED, 3)
    for x, y in zip(a, b):
        assert all(np.array_equal(u, v) for u, v in zip(x, y))
    assert not np.array_equal(a[0][0], a[1][0])
    ids = a[0][0]
    assert ids.shape == (spec["rows"], spec["seq_len"]) and ids.max() < 30000
    assert len({row.tobytes() for row in ids}) == spec["rows"]
    if spec["task"] == "causal_lm":
        assert np.array_equal(a[0][0][:, 1:], a[0][1][:, :-1])
    else:
        _, seg, mlm, nsp = a[0]
        assert ((mlm >= 0).sum(1) == spec["masked_per_row"]).all()
        assert set(np.unique(mlm[mlm < 0])) == {-100}
        assert set(np.unique(seg)) == {0, 1} and set(np.unique(nsp)) <= {0, 1}
    assert traffic_gen.tokens_per_step(spec) == 8192


# ------------------------------------------------------------- statistics
def test_percentile_and_spread():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50.5
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2], 100) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # statistics.quantiles' exclusive method: q1 = 1.75, q3 = 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_worst_leaf_gap_uses_the_median_leaf_as_a_floor():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    gap, at = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "tiny": 2e-9}, ref)
    assert at == "a" and gap == pytest.approx(0.1)
    gap, at = check.worst_leaf_gap({"a": 1.0, "b": 2.0, "tiny": 0.5}, ref)
    assert at == "tiny" and gap == pytest.approx(0.5, rel=1e-6)
    gap, _ = check.worst_leaf_gap({"a": 1.0, "b": float("nan"),
                                   "tiny": 1e-9}, ref)
    assert gap != gap
    with pytest.raises(KeyError):
        check.worst_leaf_gap({"a": 1.0}, ref)


def test_checks_print_and_fail_closed(capsys):
    c = check.Checks({"x": {"limit": 0.5}, "y": {"limit": 0.0}})
    assert not c.correct            # nothing compared is not correct
    assert c.add("x", 0.25) and c.add("y", 0.0) and c.correct
    assert not c.add("x", float("nan")) and not c.correct
    with pytest.raises(KeyError):
        c.add("z", 0.0)
    out = capsys.readouterr().out
    assert "check x value=0.25 limit=0.5 ok" in out and "FAILED" in out


def test_token_gaps():
    lg = np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 3.5]], np.float32)
    assert check.token_gaps(lg, [1, 0]).tolist() == [0.0, 0.5]


# ----------------------------------------------------------- kernel costs
def test_flash_attention_cost_by_hand():
    fa = loader.module("kernel_costs", "flash_attention")
    # one head, 4 x 4 scores of width 2: QK^T is 4*4*2 multiply-adds =
    # 64 flops, PV the same -> 128; causal needs half
    assert fa.fwd(1, 1, 4, 4, 2, False) == (128.0, 2 * 2 * 16 + 16)
    assert fa.fwd(1, 1, 4, 4, 2, True)[0] == 64.0
    assert fa.bwd(1, 1, 4, 4, 2, False)[0] == 256.0
    # gpt2-medium's call: 8 x 16 heads x 1024^2 x 64, causal
    flops, nbytes = fa.fwd(8, 16, 1024, 1024, 64, True)
    assert flops == 2 * 2 * 8 * 16 * 1024 * 1024 * 64 / 2
    assert nbytes == 4 * 8 * 16 * 1024 * 64 * 2 + 4 * 8 * 16 * 1024


def test_fused_optimizer_cost_by_hand():
    fo = loader.module("kernel_costs", "fused_optimizer")
    # bf16 param: grad 2 + master 4 + m 4 + v 4 in, master m v 12 + 2 out
    assert fo.step_bytes(1, 0) == 28
    assert fo.step_bytes(0, 1) == 28
    assert fo.step_bytes(10, 5) == 420


def test_paged_attention_cost_by_hand():
    pa = loader.module("kernel_costs", "ragged_paged_attention")
    # 100 resident tokens, 16 heads of 64 floats: k and v = 2*100*16*64*4
    assert pa.call_bytes(100, 16, 64, 0, 16) == 819200
    assert pa.call_bytes(0, 16, 64, 1, 16) == 2 * 16 * 64 * 4


def test_roofline_arithmetic():
    from perf import readers
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert readers.least_seconds(200.0, 10.0, peaks) == (2.0, "compute")
    assert readers.least_seconds(50.0, 10.0, peaks) == (1.0, "bandwidth")
    assert readers.roofline_share(1.0, 4.0) == 25.0
    assert readers.roofline_share(1.0, 0.0) is None


# ------------------------------------------------------------ the reducer
def test_interval_arithmetic():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert tr.merge(iv) == [[0, 20], [30, 40]]
    assert tr.busy_ns(iv, 0, 50) == 30 and tr.busy_ns(iv, 15, 35) == 10
    assert tr.idle_gaps(iv, 0, 50) == [(20, 30), (40, 50)]
    assert tr.idle_gaps([], 3, 9) == [(3, 9)]


def test_gaps_go_to_the_innermost_covering_span():
    spans = [("step", 0, 100), ("readback", 60, 90), ("feed", 110, 120)]
    got = tr.attribute([(10, 20), (50, 70), (95, 115), (130, 140)], spans)
    assert got == {"step": 10 + 10 + 5, "readback": 10,
                   "between_spans": 10 + 10, "feed": 5}


def test_self_time_leaves_out_the_children():
    ops = [("while", 0, 100), ("fusion", 10, 40), ("kernel", 50, 90),
           ("copy", 120, 130)]
    assert tr.self_times(ops) == {"while": 30, "fusion": 30, "kernel": 40,
                                  "copy": 10}


def test_kernels_are_found_by_name_and_nested_matches_dropped():
    ops = [("%flash_attention_fwd.24 bf16[8,16,1024,64]", 0, 10),
           ("%fusion.2 f32[8]", 10, 20),
           ("%flash_attention_bwd.3 bf16[8,16,1024,64]", 20, 50),
           ("%flash_attention_bwd.inner", 25, 30)]
    assert tr.kernel_events(ops, "flash_attention_fwd") == [(0, 10)]
    assert tr.kernel_events(ops, "flash_attention_bwd") == [(20, 50)]
    assert tr.kernel_events(ops, "fused_optimizer") == []


def test_short_names_of_device_events():
    text = ('%flash_attention_fwd.24 = (bf16[8,16,1024,64]{3,2,1,0:T(8,128)'
            '(2,1)S(1)}, f32[8,16,1024,8]{3,2,1,0:T(8,128)}) custom-call('
            'bf16[8,16,1024,64]{3,2,1,0} %bitcast.3289), custom_call_target='
            '"tpu_custom_call"')
    assert tr.short_name(text) == "%flash_attention_fwd.24 bf16[8,16,1024,64]"
    assert tr.short_name("%fusion.3476 = s32[1,8,8,128]{3,2,1,0} fusion("
                         "s32[8,1024]{1,0} %v)") == "%fusion.3476 s32[1,8,8,128]"
    assert tr.short_name("%copy-done.641 = u32[]{:S(2)} copy-done(%x)") \
        == "%copy-done.641 u32[]"
    assert tr.short_name("plain") == "plain"


def test_recorded_trace():
    """A stretch of gpt2-medium.pretrain's trace from the v5e, cut to
    two steps (tests/perf/data/recorded_trace.json)."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        rec = json.load(f)
    trace = tr.Trace(rec["raw"])
    want = rec["expect"]
    assert trace.chips() == [0]
    assert trace.window_s == pytest.approx(want["window_s"])
    assert trace.busy_s() == pytest.approx(want["busy_s"])
    assert 0 < trace.busy_s() < trace.window_s
    for kernel, (calls, seconds) in want["kernels"].items():
        got = trace.kernel_seconds(kernel)
        assert got[0] == calls and got[1] == pytest.approx(seconds)
    assert len(trace.spans_named("train_step")) == want["train_steps"]
    bd = trace.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    idle = sum(t for _, t in bd["idle_gaps"])
    assert idle <= trace.window_s - trace.busy_s() + 1e-9
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]


def test_spreads_of_sets(tmp_path):
    from perf import spreads

    def line(v, ok=True):
        return json.dumps({"correct": ok, "attempted": 1, "failed": 0,
                           "metrics": {"m": {"value": v, "unit": "ms"}},
                           "device": {}})
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_text("noise\n" + "\n".join(line(v) for v in (1, 2, 3, 4, 5, 6)))
    b.write_text("\n".join(line(v, v != 10) for v in (10, 10, 10, 10)))
    sets = [spreads.read_set(str(p)) for p in (a, b)]
    assert [w for _, w in sets] == [0, 4]
    got = spreads.summarize([v for v, _ in sets])["m"]
    assert got["medians"] == [3.5, 10]
    assert got["spreads"] == pytest.approx([1.0, 0.0])
    assert got["widest"] == pytest.approx(1.0)
