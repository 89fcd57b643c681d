"""Observability runtime (ISSUE 8): metrics registry semantics
(buckets, merge, Prometheus golden, flag-off no-op), the structured
event ring + flight recorder (wraparound, dump-on-drill, clean runs
dump nothing), engine ``stats`` backward compatibility over the
registry re-backing, timeline histograms, and training step telemetry.

Everything here is model-free and fast except the two engine drills,
which reuse the session tiny GPT (``tests/conftest.py serving_gpt``)
and the geometries the serving suite already compiled.
"""
import json
import math
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.observability.metrics import Registry


@pytest.fixture
def gpt(serving_gpt):
    return serving_gpt


@pytest.fixture
def metrics_on():
    """Force the metrics flag on for one test, restoring after."""
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


# ==========================================================================
# metrics core
# ==========================================================================

def test_histogram_bucket_edges_and_observe(metrics_on):
    h = Registry().histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 50.0, 1000.0):
        h.observe(v)
    # le semantics: v <= edge lands in that bucket; overflow is last
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6 and h.sum == pytest.approx(1066.5)
    assert h.mean == pytest.approx(1066.5 / 6)
    # the default latency buckets are fixed, log-spaced, increasing
    edges = obs.LATENCY_BUCKETS_MS
    assert list(edges) == sorted(edges) and len(set(edges)) == len(edges)
    ratios = [edges[i + 1] / edges[i] for i in range(len(edges) - 1)]
    assert all(abs(r - ratios[0]) < 1e-3 for r in ratios)  # log-spaced
    with pytest.raises(ValueError, match="increasing"):
        Registry().histogram("bad", buckets=(10.0, 1.0))


def test_histogram_merge(metrics_on):
    r = Registry()
    a = r.histogram("a", buckets=(1.0, 10.0))
    b = r.histogram("b", buckets=(1.0, 10.0))
    for v in (0.5, 5.0):
        a.observe(v)
    for v in (5.0, 50.0):
        b.observe(v)
    a.merge(b)
    assert a.counts == [1, 2, 1] and a.count == 4
    assert a.sum == pytest.approx(60.5)
    c = r.histogram("c", buckets=(2.0, 20.0))
    with pytest.raises(ValueError, match="different buckets"):
        a.merge(c)


def test_registry_get_or_create_and_snapshot(metrics_on):
    r = Registry()
    assert r.counter("x.n") is r.counter("x.n")       # same identity
    with pytest.raises(ValueError, match="already registered"):
        r.gauge("x.n")
    r.counter("x.n").inc(3)
    r.gauge("x.g").set(1.5)
    r.gauge("x.lazy").set_function(lambda: 7)          # read at snap
    r.counter("x.lab", labels={"reason": "stop"}).inc()
    snap = r.snapshot()
    assert snap["x"]["n"] == 3
    assert snap["x"]["g"] == 1.5
    assert snap["x"]["lazy"] == 7
    assert snap["x"]["lab"] == {"reason=stop": 1}


def test_prometheus_text_golden(metrics_on):
    """Exact text: stable ordering (sorted names, sorted label sets),
    cumulative histogram buckets with +Inf, HELP/label escaping."""
    r = Registry()
    r.counter("req.total", help='served "requests"\nall').inc(5)
    r.gauge("pool.free").set(3)
    h = r.histogram("lat.ms", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(2.0)
    h.observe(99.0)
    r.counter("req.by", labels={"reason": 'a"b\\c'}).inc(2)
    assert r.render_prometheus() == (
        '# TYPE lat_ms histogram\n'
        'lat_ms_bucket{le="1"} 1\n'
        'lat_ms_bucket{le="10"} 2\n'
        'lat_ms_bucket{le="+Inf"} 3\n'
        'lat_ms_sum 101.5\n'
        'lat_ms_count 3\n'
        '# TYPE pool_free gauge\n'
        'pool_free 3\n'
        '# TYPE req_by counter\n'
        'req_by{reason="a\\"b\\\\c"} 2\n'
        '# HELP req_total served "requests"\\nall\n'
        '# TYPE req_total counter\n'
        'req_total 5\n')


def test_flag_off_is_noop_and_always_records():
    old = paddle.get_flags("metrics")["metrics"]
    r = Registry()
    c = r.counter("c")
    a = r.counter("a", always=True)     # stats-contract counters
    h = r.histogram("h")
    g = r.gauge("g")
    try:
        paddle.set_flags({"metrics": False})
        c.inc(5)
        h.observe(1.0)
        g.set(2.0)
        a.inc(5)
        assert c.value == 0 and h.count == 0 and g.value == 0.0
        assert a.value == 5                     # always-on contract
        obs.events.clear()
        obs.emit("k", x=1)
        assert obs.tail() == []                 # ring is gated too
        assert obs.dump("nope") is None         # ...and so are dumps
        paddle.set_flags({"metrics": True})
        c.inc(5)
        h.observe(1.0)
        assert c.value == 5 and h.count == 1
    finally:
        paddle.set_flags({"metrics": old})


# ==========================================================================
# event ring + flight recorder
# ==========================================================================

def test_event_ring_wraparound(metrics_on):
    from paddle_tpu.observability.events import EventRing
    ring = EventRing(capacity=4)
    for i in range(10):
        ring.emit("k", i=i)
    got = ring.tail()
    assert len(got) == 4
    assert [e["i"] for e in got] == [6, 7, 8, 9]       # oldest dropped
    assert [e["seq"] for e in got] == [6, 7, 8, 9]     # seq monotone
    assert ring.tail(2) == got[-2:]
    ring.clear()
    assert ring.tail() == []


def test_flight_dump_roundtrip(tmp_path, metrics_on, monkeypatch):
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    obs.events.clear()
    obs.emit("serving.enqueued", rid=7)
    err = ValueError("boom")
    path = obs.dump("unit_test", error=err, extra={"rid": 7})
    assert path and os.path.dirname(path) == str(tmp_path)
    assert obs.last_dump() == path
    rec = json.load(open(path))
    assert rec["reason"] == "unit_test"
    assert "boom" in rec["error"]
    assert rec["extra"] == {"rid": 7}
    assert any(e["kind"] == "serving.enqueued" and e["rid"] == 7
               for e in rec["events"])


def test_ring_collects_retry_guard_and_fault_events(metrics_on):
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.guard import StepGuard
    from paddle_tpu.resilience.retry import retry_call

    obs.events.clear()
    faults.clear()
    try:
        # retry attempts
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise ConnectionError("transient")
            return "ok"

        assert retry_call(flaky, max_attempts=3,
                          sleep=lambda s: None) == "ok"
        # fault firings
        faults.inject("nan_step", match="1")
        assert faults.check("nan_step", "1")
        # StepGuard skip
        StepGuard(max_bad_steps=3).observe(float("nan"))
        kinds = [e["kind"] for e in obs.tail()]
        assert kinds.count("retry.attempt") == 2
        assert "fault.fired" in kinds
        assert "guard.step_skip" in kinds
    finally:
        faults.clear()


# ==========================================================================
# engine: stats parity, metrics(), flight recorder on the nan drill
# ==========================================================================

_STAT_KEYS = [
    # counter block (declaration order == the pre-observability dict)
    "admitted", "retired", "steps", "mixed_steps", "decode_dispatches",
    "tokens_generated", "pages_allocated", "peak_pages_in_use",
    "preemptions", "timeouts", "cancelled", "failed", "rejected",
    "retries", "cache_hits", "cache_hit_tokens",
    "prefill_tokens_requested", "prefill_tokens_computed",
    # live gauges appended by the stats property
    "cached_pages", "evictions", "pages_in_use", "pages_free",
    "queue_depth", "kv_quant", "kv_page_bytes", "kv_bytes_in_use",
    # speculative decoding (ISSUE 9) — strictly APPENDED so every
    # pre-existing key keeps its position
    "spec_proposed", "spec_accepted", "spec_accept_rate",
    # live migration (ISSUE 20) — strictly APPENDED, same contract
    "migrated_in", "migrated_out",
]


def _drive(gpt, prompts, new):
    eng = ContinuousBatchingEngine(gpt, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=4,
                                   prefill_chunk=8, q_block=2)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    return eng, rids, done


def test_engine_stats_backward_compat(gpt):
    """The registry re-backing is invisible through ``stats``: same
    keys, same order, same int values — and the numbers are identical
    with PDTPU_METRICS off (always=True counters keep the contract)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12)]
    new = [6, 4, 7, 5]
    old = paddle.get_flags("metrics")["metrics"]
    try:
        paddle.set_flags({"metrics": True})
        _drive(gpt, prompts, new)   # warm the model's program cache:
        # the first engine on a cold model pays one extra scalar decode
        # dispatch to compile (steps/decode_dispatches +1) — that is
        # cache warmness, not flag behavior, so take it off the table
        eng_on, _, done_on = _drive(gpt, prompts, new)
        paddle.set_flags({"metrics": False})
        eng_off, _, done_off = _drive(gpt, prompts, new)
    finally:
        paddle.set_flags({"metrics": old})
    st_on, st_off = eng_on.stats, eng_off.stats
    assert list(st_on) == _STAT_KEYS == list(st_off)
    assert st_on == st_off                   # flag changes NOTHING here
    for k in _STAT_KEYS:
        if k not in ("kv_quant", "spec_accept_rate"):
            assert isinstance(st_on[k], int), k
    # ...and the off engine's outputs match the on engine's bitwise
    for rid in done_on:
        np.testing.assert_array_equal(done_on[rid].sequence,
                                      done_off[rid].sequence)
    assert st_on["admitted"] == 4 and st_on["retired"] == 4


def test_engine_metrics_timelines_populated(gpt, metrics_on):
    """The slot-contention workload (4 requests through 2 slots) fills
    the timeline histograms: one TTFT/queue observation per request,
    TPOT for every multi-token stream, finish-reason labeled counters,
    and per-dispatch latency."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12)]
    new = [6, 4, 7, 5]
    eng, rids, done = _drive(gpt, prompts, new)
    m = eng.metrics()["serving"]
    assert m["ttft_ms"]["count"] == 4
    assert m["queue_ms"]["count"] == 4
    assert m["tpot_ms"]["count"] == 4        # every stream has >= 2 toks
    assert m["ttft_ms"]["sum"] > 0 and m["tpot_ms"]["sum"] >= 0
    assert m["finished"] == {"reason=length": 4}
    assert m["decode_tokens_per_window"]["count"] >= 1
    # one dispatch_ms observation per engine dispatch (mixed steps are
    # counted inside decode_dispatches)
    assert m["dispatch_ms"]["count"] == eng.stats["decode_dispatches"]
    # stats counters surface in the same snapshot (registry-backed)
    assert m["tokens_generated"] == eng.stats["tokens_generated"]
    # prometheus rendering of the same registry is non-empty and stable
    text = eng.render_prometheus()
    assert "serving_ttft_ms_bucket" in text
    assert text == eng.render_prometheus()
    # queue time is sane: later requests waited for a slot
    assert m["queue_ms"]["sum"] >= 0
    # all timelines closed: no open-request leak
    assert eng._tl._open == {}


def test_flight_recorder_on_nan_drill(gpt, tmp_path, monkeypatch,
                                      metrics_on):
    """Acceptance drill: ``engine_nan_decode`` produces a flight dump
    containing the victim's admission and decode timeline; an identical
    clean run dumps nothing."""
    from paddle_tpu.core import errors
    from paddle_tpu.resilience import faults

    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    rng = np.random.default_rng(13)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)

    # clean run first: zero dumps
    obs.events.clear()
    eng = ContinuousBatchingEngine(gpt, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=4,
                                   prefill_chunk=8, q_block=2)
    eng.add_request(p1, 8)
    eng.run()
    assert os.listdir(tmp_path) == []

    faults.clear()
    obs.events.clear()
    try:
        eng = ContinuousBatchingEngine(gpt, max_slots=2, page_size=8,
                                       max_seq_len=32, decode_window=4,
                                       prefill_chunk=8, q_block=2)
        r1 = eng.add_request(p1, 8)
        r2 = eng.add_request(p2, 8)
        # at=3: dispatches 1-2 are the mixed prefill steps, so the
        # poison lands in a DECODE WINDOW — the dump must show the
        # victim's decode phase, not just its prefill
        faults.inject("engine_nan_decode", match=str(r1), at=3)
        done = eng.run()
        assert done[r1].finish_reason == "failed"
        assert isinstance(done[r1].error, errors.NonFiniteLogitsError)
        assert done[r2].finish_reason == "length"
    finally:
        faults.clear()
    dumps = sorted(os.listdir(tmp_path))
    assert len(dumps) == 1                       # one failure, one dump
    rec = json.load(open(os.path.join(tmp_path, dumps[0])))
    assert rec["reason"] == "nan_decode"
    assert rec["error_code"] == "PDT-E018"
    assert rec["extra"]["rid"] == r1
    evs = rec["events"]
    by_kind = {}
    for e in evs:
        by_kind.setdefault(e["kind"], []).append(e)
    # the victim's full story is in the ring: enqueue, admission,
    # prefill, first token, the injected poison, and the retirement
    assert any(e["rid"] == r1 for e in by_kind["serving.enqueued"])
    assert any(e["rid"] == r1 for e in by_kind["serving.admitted"])
    assert any(e["rid"] == r1 for e in by_kind["serving.prefill_chunk"])
    assert any(e["rid"] == r1 for e in by_kind["serving.first_token"])
    assert any(e["rid"] == r1 for e in by_kind["serving.nan_poison"])
    assert any(e["rid"] == r1 and e["finish_reason"] == "failed"
               for e in by_kind["serving.retired"])
    # decode-phase evidence: the dump is written mid-window (at the
    # guard failure), so the decode DISPATCH events are what it holds
    assert any(e["name"] in ("window", "decode")
               for e in by_kind["serving.dispatch"])
    assert any(e["site"] == "engine_nan_decode"
               for e in by_kind["fault.fired"])


# ==========================================================================
# training telemetry
# ==========================================================================

def test_steptimer_records_and_counts_retraces(metrics_on):
    r = Registry()
    st = obs.StepTimer(registry=r, n_params=1000, peak_flops=1e12,
                       log_every=0)
    st.mark()
    st.step(tokens=512, trace_count=1)      # first: compile baseline
    st.step(tokens=512, trace_count=1)
    st.step(tokens=512, trace_count=3)      # 2 retraces past baseline
    snap = r.snapshot()["train"]
    assert snap["steps"] == 3
    assert snap["step_ms"]["count"] == 3
    assert snap["retraces"] == 2
    assert snap["tokens_per_sec"] > 0
    assert snap["mfu"] == pytest.approx(
        6.0 * 1000 * snap["tokens_per_sec"] / 1e12, rel=1e-3)


def test_fit_populates_global_registry(metrics_on):
    import paddle_tpu.nn as nn

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            return self.fc(x)

    net = Net()
    m = paddle.hapi.Model(net)
    m.prepare(paddle.optimizer.Adam(parameters=net.parameters()),
              loss=nn.loss.CrossEntropyLoss())
    xs = np.random.default_rng(0).random((16, 8)).astype("float32")
    ys = np.zeros((16, 1), "int64")
    ds = paddle.io.TensorDataset([paddle.to_tensor(xs),
                                  paddle.to_tensor(ys)])
    reg = obs.registry()
    steps0 = reg.counter("train.steps").value
    hist0 = reg.histogram("train.step_ms").count
    m.fit(ds, batch_size=8, epochs=1, verbose=0)
    assert reg.counter("train.steps").value == steps0 + 2
    assert reg.histogram("train.step_ms").count == hist0 + 2
    assert reg.gauge("train.tokens_per_sec").value > 0


@pytest.fixture
def fused_opt_on():
    """The fused optimizer path is the default, but a flag is the
    process's: a test of another file that builds a benchmark program
    in this worker (``perf/models/common.TrainProgram`` sets the
    configuration's ``fused_optimizer``) leaves it off."""
    old = paddle.get_flags("fused_opt")["fused_opt"]
    paddle.set_flags({"fused_opt": True})
    yield
    paddle.set_flags({"fused_opt": old})


def test_eager_optimizer_step_telemetry(metrics_on, fused_opt_on):
    import paddle_tpu.nn as nn
    reg = obs.registry()
    h0 = reg.histogram("train.opt_step_ms").count
    net = nn.Linear(8, 8)
    opt = paddle.optimizer.Adam(parameters=net.parameters())
    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    for _ in range(2):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    assert reg.histogram("train.opt_step_ms").count == h0 + 2
    # the fused path dispatched one kernel per dtype bucket per step
    assert reg.counter("train.fused_bucket_dispatches").value >= 2
