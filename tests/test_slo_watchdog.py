"""SLO guardrails and stall watchdog (ISSUE 14): shared percentile
math, burn-rate window math on a fake clock, SLO pass/breach on slot-contention traffic through the session
tiny GPT, the ``engine_stall`` drill (coded ``EngineStallError`` within
the deadline, exactly one flight dump holding thread stacks and the
victim's timeline, zero dumps + nothing armed on clean runs,
co-residents bitwise), flight-dump keep-last-K retention and
metrics-off no-op parity.

Engine tests reuse the session ``serving_gpt`` and the exact geometry
the serving suite already compiled (max_slots=2/page_size=8/...), so
they ride cached programs — tier-1 budget, not semantics.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.core import errors
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.observability import watchdog as wdog
from paddle_tpu.observability.metrics import (LATENCY_BUCKETS_MS,
                                              Registry,
                                              percentile_from_counts)
from paddle_tpu.observability.slo import SLOEngine, SLOSpec, parse_slo
from paddle_tpu.resilience import faults

# the geometry every serving suite compiles against (conftest comment)
_KW = dict(max_slots=2, page_size=8, max_seq_len=32, decode_window=4,
           prefill_chunk=8, q_block=2)


@pytest.fixture
def gpt(serving_gpt):
    return serving_gpt


@pytest.fixture
def metrics_on():
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


def _prompts(seed=0, sizes=(5, 9)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 96, (n,)).astype(np.int32) for n in sizes]


# ==========================================================================
# shared percentile math
# ==========================================================================

def test_histogram_percentile(metrics_on):
    h = Registry().histogram("h", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 2.0, 3.0, 20.0):
        h.observe(v)
    # q=0.5 -> 2nd of 4 observations -> the (1,10] bucket's upper edge
    assert h.percentile(0.5) == 10.0
    assert h.percentile(0.25) == 1.0
    assert h.percentile(1.0) == 100.0
    h.observe(1000.0)             # overflow bucket: no finite edge
    assert h.percentile(1.0) == float("inf")
    assert Registry().histogram("e").percentile(0.99) == 0.0
    # the module function is the same math over raw state
    assert percentile_from_counts(h.buckets, h.counts, h.count,
                                  0.5) == h.percentile(0.5)


# ==========================================================================
# SLO engine: spec parse + burn-rate window math (fake clock)
# ==========================================================================

def test_parse_slo():
    specs = parse_slo("ttft_p95_ms=500, tpot_p99_ms=100; goodput=0.99")
    by = {s.name: s for s in specs}
    assert by["ttft_p95_ms"].metric == "serving.ttft_ms"
    assert by["ttft_p95_ms"].threshold == 500.0
    assert by["ttft_p95_ms"].budget == pytest.approx(0.05)
    assert by["tpot_p99_ms"].budget == pytest.approx(0.01)
    assert by["goodput"].kind == "ratio"
    assert by["goodput"].objective == 0.99
    assert by["goodput"].budget == pytest.approx(0.01)
    assert parse_slo("") == [] and parse_slo(None) == []
    assert len(parse_slo(specs)) == 3          # list passthrough
    with pytest.raises(ValueError, match="unknown SLO spec"):
        parse_slo("ttft_p95=500")
    with pytest.raises(ValueError, match="objective"):
        SLOSpec("g", "serving.finished", kind="ratio", objective=1.5)


def test_slo_burn_rate_window_math(metrics_on):
    """Exact multi-window burn-rate accounting on a fake clock: fast
    window reacts, slow window confirms, breach fires once on the
    transition, recovery clears it, budget_remaining tracks the slow
    window's bad fraction against the budget."""
    t = [0.0]
    reg = Registry()
    h = reg.histogram("serving.ttft_ms", buckets=LATENCY_BUCKETS_MS)
    spec = SLOSpec("ttft_p95_ms", "serving.ttft_ms", threshold=10.0,
                   percentile=0.95, fast_window_s=10.0,
                   slow_window_s=60.0)
    breaches = []
    slo = SLOEngine(reg, [spec], clock=lambda: t[0],
                    on_breach=breaches.append)
    # 100 good observations at t=1
    t[0] = 1.0
    for _ in range(100):
        h.observe(1.0)
    (st,) = slo.evaluate()
    assert st["ok"] and not st["breached"]
    assert st["burn_fast"] == 0.0 and st["budget_remaining"] == 1.0
    # t=5: 50 bad observations -> fast window sees 50/150 bad
    t[0] = 5.0
    for _ in range(50):
        h.observe(1000.0)
    (st,) = slo.evaluate()
    assert st["burn_fast"] == pytest.approx((50 / 150) / 0.05)
    assert st["burn_slow"] == pytest.approx((50 / 150) / 0.05)
    assert st["breached"] and not st["ok"]
    assert st["value"] > 10.0                 # windowed p95 is bad
    assert st["budget_remaining"] == 0.0
    assert len(breaches) == 1                 # transition, not per-eval
    (st,) = slo.evaluate()
    assert st["breached"] and len(breaches) == 1
    # t=120: both windows have rolled past the bad burst; fresh good
    # traffic -> burn 0, recovered
    t[0] = 120.0
    for _ in range(20):
        h.observe(1.0)
    (st,) = slo.evaluate()
    assert st["burn_fast"] == 0.0 and st["burn_slow"] == 0.0
    assert st["ok"] and not st["breached"]
    assert st["budget_remaining"] == 1.0
    assert len(breaches) == 1
    kinds = [e["kind"] for e in obs.tail()]
    assert "slo.breach" in kinds and "slo.recovered" in kinds
    # budget gauges render through the registry
    assert "slo_budget_remaining" in reg.render_prometheus()


def test_slo_ratio_goodput(metrics_on):
    t = [0.0]
    reg = Registry()
    spec = SLOSpec("goodput", "serving.finished", kind="ratio",
                   objective=0.9, fast_window_s=10.0,
                   slow_window_s=60.0)
    slo = SLOEngine(reg, [spec], clock=lambda: t[0])
    good = reg.counter("serving.finished", labels={"reason": "length"})
    bad = reg.counter("serving.finished", labels={"reason": "timeout"})
    t[0] = 1.0
    good.inc(98)
    bad.inc(2)
    (st,) = slo.evaluate()
    assert st["ok"] and st["value"] == pytest.approx(0.98)
    assert st["burn_slow"] == pytest.approx(0.02 / 0.1)
    t[0] = 2.0
    bad.inc(50)                    # timeouts burn the goodput budget
    (st,) = slo.evaluate()
    assert not st["ok"] and st["breached"]
    assert st["value"] == pytest.approx(98 / 150)


# ==========================================================================
# engine integration: pass / breach / flight dump / prometheus
# ==========================================================================

def test_engine_slo_pass_and_breach(gpt, tmp_path, monkeypatch,
                                    metrics_on):
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    obs.events.clear()
    # generous objectives: slot-contention traffic passes them
    eng = ContinuousBatchingEngine(
        gpt, **_KW, slo="ttft_p95_ms=100000,goodput=0.5")
    for p in _prompts():
        eng.add_request(p, 6)
    eng.run()
    sts = eng.slo_status()
    assert [s["name"] for s in sts] == ["ttft_p95_ms", "goodput"]
    assert all(s["ok"] and not s["breached"] for s in sts)
    assert all(s["budget_remaining"] == 1.0 for s in sts)
    assert sts[1]["window_total"] == 2        # both requests retired ok
    assert "slo_budget_remaining" in eng.render_prometheus()
    assert os.listdir(tmp_path) == []         # no dump on a clean pass

    # impossible objective: every TTFT observation violates it ->
    # burn-rate breach on both windows -> slo.breach + ONE flight dump
    eng2 = ContinuousBatchingEngine(gpt, **_KW,
                                    slo="ttft_p95_ms=0.000001")
    for p in _prompts(seed=1):
        eng2.add_request(p, 6)
    eng2.run()
    (st,) = eng2.slo_status()
    assert st["breached"] and not st["ok"]
    assert st["burn_slow"] > 1.0 and st["budget_remaining"] == 0.0
    dumps = [f for f in sorted(os.listdir(tmp_path))
             if f.endswith(".json") and not f.endswith(".trace.json")]
    assert len(dumps) == 1                    # one transition, one dump
    rec = json.load(open(os.path.join(tmp_path, dumps[0])))
    assert rec["reason"] == "slo_breach"
    assert rec["extra"]["name"] == "ttft_p95_ms"
    assert any(e["kind"] == "slo.breach" for e in rec["events"])


# ==========================================================================
# stall watchdog: the engine_stall drill + clean-run disarm
# ==========================================================================

def test_engine_stall_drill(gpt, tmp_path, monkeypatch, metrics_on):
    """Acceptance drill: a deliberately-hung dispatch produces a coded
    EngineStallError within the deadline, exactly one flight dump
    containing thread stacks and the victim's lifecycle events, zero
    dumps on the clean run, and co-resident requests complete bitwise
    against the clean run."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    faults.clear()
    obs.events.clear()
    prompts = _prompts(seed=3)

    # clean run, watchdog armed: nothing fires, nothing stays armed
    eng = ContinuousBatchingEngine(gpt, **_KW, watchdog_ms=10000)
    rids = [eng.add_request(p, 6) for p in prompts]
    done_clean = eng.run()
    assert os.listdir(tmp_path) == []
    assert wdog.armed() == []

    deadline_ms = 300.0
    faults.inject("engine_stall", match="mixed", at=2)
    try:
        eng2 = ContinuousBatchingEngine(gpt, **_KW,
                                        watchdog_ms=deadline_ms)
        rids2 = [eng2.add_request(p, 6) for p in prompts]
        done, n_raised = {}, 0
        t0 = time.monotonic()
        while eng2.has_work:
            try:
                cs = eng2.step()
            except errors.EngineStallError as e:
                n_raised += 1
                # coded, and within the deadline (+ poll + slack)
                assert e.error_code == "PDT-E020"
                assert "mixed" in str(e)
                assert time.monotonic() - t0 < 10.0
                continue
            for c in cs:
                done[c.request_id] = c
    finally:
        faults.clear()
    assert n_raised == 1
    assert wdog.armed() == []
    # co-residents complete bitwise: the stalled dispatch never ran,
    # so the re-planned dispatch reproduces the clean stream exactly
    for r1, r2 in zip(rids, rids2):
        np.testing.assert_array_equal(done_clean[r1].sequence,
                                      done[r2].sequence)
    recs = [f for f in sorted(os.listdir(tmp_path))
            if f.endswith(".json") and not f.endswith(".trace.json")]
    assert len(recs) == 1                     # exactly one flight dump
    rec = json.load(open(os.path.join(tmp_path, recs[0])))
    assert rec["reason"] == "watchdog_stall"
    assert rec["extra"]["site"] == "serving.dispatch"
    assert rec["extra"]["key"] == "mixed"
    # thread stacks captured, including the stalled dispatch frame
    stacks = rec["extra"]["stacks"]
    assert stacks and any("simulated_stall" in s
                          for s in stacks.values())
    kinds = [e["kind"] for e in rec["events"]]
    assert "watchdog.stall" in kinds
    # the victims' lifecycle is in the dump: enqueue + admission of
    # both co-resident requests, and the drill's fault firing
    for want in ("serving.enqueued", "serving.admitted", "fault.fired"):
        assert want in kinds, want
    enq = [e["rid"] for e in rec["events"]
           if e["kind"] == "serving.enqueued"]
    assert set(rids2) <= set(enq)


def test_watchdog_heartbeat_and_fit_disarm(tmp_path, monkeypatch,
                                           metrics_on):
    """Heartbeats hold a slow-but-alive operation past its deadline
    without firing; a fit armed via the watchdog_stall_ms flag
    disarms cleanly (zero dumps, nothing armed)."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    token = wdog.arm("unit.op", 120.0, key="hb")
    for _ in range(4):
        time.sleep(0.06)
        token.heartbeat()
    assert not token.fired
    token.disarm()
    assert wdog.armed() == []

    import paddle_tpu.nn as nn
    old = paddle.get_flags("watchdog_stall_ms")["watchdog_stall_ms"]
    paddle.set_flags({"watchdog_stall_ms": 60000.0})
    try:
        net = nn.Linear(8, 4)
        m = paddle.hapi.Model(net)
        m.prepare(paddle.optimizer.Adam(parameters=net.parameters()),
                  loss=nn.loss.CrossEntropyLoss())
        xs = np.random.default_rng(0).random((8, 8)).astype("float32")
        ys = np.zeros((8, 1), "int64")
        ds = paddle.io.TensorDataset([paddle.to_tensor(xs),
                                      paddle.to_tensor(ys)])
        m.fit(ds, batch_size=4, epochs=1, verbose=0)
    finally:
        paddle.set_flags({"watchdog_stall_ms": old})
    assert wdog.armed() == []                 # disarm on clean runs
    assert os.listdir(tmp_path) == []


def test_watchdog_fires_and_rearms_on_heartbeat(tmp_path, monkeypatch,
                                                metrics_on):
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    obs.events.clear()
    token = wdog.arm("unit.op", 80.0, key="stall")
    deadline = time.monotonic() + 5.0
    # dump_path is set at the END of the fire sequence (the interrupt
    # goes out before the dump's file IO), so wait on it, not on fired
    while token.dump_path is None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert token.fired
    assert token.dump_path and os.path.exists(token.dump_path)
    assert any(e["kind"] == "watchdog.stall" and e["key"] == "stall"
               for e in obs.tail())
    token.heartbeat()                         # re-arm clears the latch
    assert not token.fired
    token.disarm()


# ==========================================================================
# flight-dump retention (satellite: keep-last-K GC)
# ==========================================================================

def test_flight_dump_retention(tmp_path, monkeypatch, metrics_on):
    """Watchdog/SLO/NaN dumps all funnel through events.dump, so the
    keep-last-K cap (flight_keep flag / PDTPU_FLIGHT_KEEP) bounds the
    dir no matter who dumps; companion files die with their record."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    old = paddle.get_flags("flight_keep")["flight_keep"]
    paddle.set_flags({"flight_keep": 3})
    try:
        paths = []
        for i in range(6):
            p = obs.dump(f"retention_{i}")
            assert p is not None
            paths.append(p)
            # companion like the watchdog writes next to its record
            open(p[:-len(".json")] + ".trace.json", "w").write("{}")
            # distinct mtimes (same-second dumps tie-break by name,
            # which is already seq order; make age explicit anyway)
            os.utime(p, (1_000_000 + i, 1_000_000 + i))
        recs = [f for f in sorted(os.listdir(tmp_path))
                if f.endswith(".json")
                and not f.endswith(".trace.json")]
        assert len(recs) == 3
        # the newest three survived, companions of the dead are gone
        assert os.path.basename(paths[-1]) in recs
        assert os.path.basename(paths[0]) not in recs
        assert not os.path.exists(paths[0][:-len(".json")]
                                  + ".trace.json")
        assert os.path.exists(paths[-2][:-len(".json")]
                              + ".trace.json")
    finally:
        paddle.set_flags({"flight_keep": old})


# ==========================================================================
# metrics-off no-op parity
# ==========================================================================

def test_metrics_off_guardrails_noop(gpt, tmp_path, monkeypatch):
    """With PDTPU_METRICS off, slo=/watchdog_ms= arm NOTHING: outputs
    match the guardrail-free engine bitwise, slo_status is empty, no
    dumps are written, and watchdog.arm returns the null token."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    prompts = _prompts(seed=5)
    old = paddle.get_flags("metrics")["metrics"]
    try:
        paddle.set_flags({"metrics": True})
        eng_ref = ContinuousBatchingEngine(gpt, **_KW)
        r_ref = [eng_ref.add_request(p, 6) for p in prompts]
        done_ref = eng_ref.run()
        paddle.set_flags({"metrics": False})
        assert wdog.arm("x", 100.0) is wdog.NULL_TOKEN
        eng = ContinuousBatchingEngine(
            gpt, **_KW, slo="ttft_p95_ms=0.000001", watchdog_ms=50.0)
        rids = [eng.add_request(p, 6) for p in prompts]
        done = eng.run()
        assert eng.slo_status() == []
        assert wdog.armed() == []
    finally:
        paddle.set_flags({"metrics": old})
    for a, b in zip(r_ref, rids):
        np.testing.assert_array_equal(done_ref[a].sequence,
                                      done[b].sequence)
    assert os.listdir(tmp_path) == []
