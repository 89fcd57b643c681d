"""Distributed tracing + fleet aggregation (ISSUE 12): span semantics
(nesting, exception safety, context propagation through rpc), the
Chrome/Perfetto exporter (golden JSON, stability, escaping), compile
span / retrace-cause events from the jit layer, HBM gauges,
``fleet_snapshot`` merge + skew on a simulated 8-rank fleet (including
the straggler-timeout path), flight-dump schema v2, and the
``PDTPU_METRICS=off`` cheap-no-op parity.

Everything is model-free and sub-second except the export acceptance
drill, which reuses the session tiny GPT (``conftest.serving_gpt``)
and the geometries the serving suite already compiled.
"""
import gc
import json
import math
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.observability import aggregate, tracing
from paddle_tpu.observability.metrics import Registry


@pytest.fixture
def metrics_on():
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


@pytest.fixture
def fresh_trace(metrics_on):
    """Clean ring + deterministic span/trace ids for golden output."""
    obs.events.clear()
    tracing._reset()
    yield
    tracing._reset()
    obs.events.clear()


# ==========================================================================
# span semantics
# ==========================================================================

def test_span_nesting_and_context(fresh_trace):
    with tracing.span("outer", phase="x"):
        ctx = tracing.inject()
        assert ctx == {"trace_id": 1, "span_id": 2}
        assert tracing.context_fields() == {"trace_id": 1,
                                            "parent_id": 2}
        with tracing.span("inner"):
            pass
    evs = obs.tail()
    kinds = [(e["kind"], e["name"]) for e in evs]
    assert kinds == [("span.begin", "outer"), ("span.begin", "inner"),
                     ("span.end", "inner"), ("span.end", "outer")]
    beg_outer, beg_inner, end_inner, end_outer = evs
    assert beg_outer["trace_id"] == beg_inner["trace_id"]
    assert "parent_id" not in beg_outer              # root
    assert beg_inner["parent_id"] == beg_outer["span_id"]
    assert end_inner["dur_us"] >= 0
    assert beg_outer["phase"] == "x"
    # trace closed: context empty, next root starts a NEW trace
    assert tracing.inject() is None
    with tracing.span("again"):
        assert tracing.inject()["trace_id"] != beg_outer["trace_id"]


def test_span_exception_safety(fresh_trace):
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("x")
    end = obs.tail()[-1]
    assert end["kind"] == "span.end" and end["error"] == "ValueError"
    # the stack unwound: a new span is a fresh root
    assert tracing.inject() is None
    with tracing.span("after"):
        assert "parent_id" not in obs.tail()[-1]


def test_traced_decorator(fresh_trace):
    @tracing.traced
    def work():
        return 7

    @tracing.traced("named", k=1)
    def work2():
        return 8

    assert work() == 7 and work2() == 8
    names = [e["name"] for e in obs.tail()
             if e["kind"] == "span.begin"]
    assert names == ["work", "named"]


def test_attach_reparents_spans(fresh_trace):
    with tracing.span("client"):
        ctx = tracing.inject()
    with tracing.attach(ctx), tracing.span("server"):
        pass
    beg = [e for e in obs.tail() if e["kind"] == "span.begin"]
    assert beg[1]["name"] == "server"
    assert beg[1]["trace_id"] == ctx["trace_id"]
    assert beg[1]["parent_id"] == ctx["span_id"]
    # attach scope popped cleanly
    assert tracing.inject() is None
    assert tracing.attach(None).__enter__() is not None  # no-op ok


# ==========================================================================
# Chrome trace export
# ==========================================================================

def test_render_trace_golden():
    """Exact export of a synthetic ring: span pair fused to one "X"
    complete event, serving lifecycle on slot tracks, fault event on
    the runtime track, metadata first, stable sorted JSON, standard
    escaping of a quote/newline payload."""
    events = [
        {"seq": 0, "ts": 100.0, "kind": "span.begin", "name": "compile",
         "span_id": 2, "trace_id": 1, "tname": "MainThread", "fn": "step"},
        {"seq": 1, "ts": 100.002, "kind": "span.end", "name": "compile",
         "span_id": 2, "trace_id": 1, "dur_us": 2000.0},
        {"seq": 2, "ts": 100.003, "kind": "serving.enqueued", "rid": 0,
         "prompt_len": 4, "max_new_tokens": 2},
        {"seq": 3, "ts": 100.004, "kind": "serving.admitted", "rid": 0,
         "slot": 1, "cached_tokens": 0, "resume_len": 0},
        {"seq": 4, "ts": 100.005, "kind": "fault.fired",
         "site": "engine_nan_decode", "key": 'r"0\n'},
    ]
    got = tracing.render_trace(events, rank=3, host="tpu-worker-3")
    assert got == {
        "displayTimeUnit": "ms",
        "traceEvents": [
            {"name": "process_name", "ph": "M", "pid": 3, "tid": 0,
             "args": {"name": "rank3 (tpu-worker-3)"}},
            {"name": "thread_name", "ph": "M", "pid": 3, "tid": 1,
             "args": {"name": "MainThread"}},
            {"name": "thread_name", "ph": "M", "pid": 3, "tid": 2,
             "args": {"name": "engine"}},
            {"name": "thread_name", "ph": "M", "pid": 3, "tid": 3,
             "args": {"name": "engine/slot1"}},
            {"name": "thread_name", "ph": "M", "pid": 3, "tid": 4,
             "args": {"name": "runtime"}},
            {"name": "compile", "cat": "span", "ph": "X", "ts": 0.0,
             "dur": 2000.0, "pid": 3, "tid": 1,
             "args": {"span_id": 2, "trace_id": 1, "fn": "step"}},
            {"name": "serving.enqueued", "cat": "serving", "ph": "i",
             "s": "t", "ts": 3000.0, "pid": 3, "tid": 2,
             "args": {"rid": 0, "prompt_len": 4, "max_new_tokens": 2}},
            {"name": "serving.admitted", "cat": "serving", "ph": "i",
             "s": "t", "ts": 4000.0, "pid": 3, "tid": 3,
             "args": {"rid": 0, "slot": 1, "cached_tokens": 0,
                      "resume_len": 0}},
            {"name": "fault.fired", "cat": "fault", "ph": "i",
             "s": "t", "ts": 5000.0, "pid": 3, "tid": 4,
             "args": {"site": "engine_nan_decode", "key": 'r"0\n'}},
        ],
    }
    # serialization is valid, stable JSON (escaping included)
    s1 = json.dumps(got, indent=1, sort_keys=True)
    assert json.loads(s1) == got
    assert s1 == json.dumps(tracing.render_trace(
        events, rank=3, host="tpu-worker-3"), indent=1, sort_keys=True)


def test_render_trace_unmatched_spans():
    """A begin whose end fell off the ring renders as "B" (the open
    phase a crash trace ends in); an orphan end renders as "E"."""
    events = [
        {"seq": 0, "ts": 1.0, "kind": "span.begin", "name": "hung",
         "span_id": 9, "trace_id": 5, "tname": "MainThread"},
        {"seq": 1, "ts": 1.5, "kind": "span.end", "name": "lost",
         "span_id": 8, "trace_id": 5, "dur_us": 10.0},
    ]
    evs = tracing.render_trace(events)["traceEvents"]
    phases = {e["name"]: e["ph"] for e in evs if e["ph"] in "BE"}
    assert phases == {"hung": "B", "lost": "E"}


def test_export_trace_acceptance(serving_gpt, fresh_trace, tmp_path):
    """ISSUE 12 acceptance: export of a serving-engine run + a 2-rank
    CPU-mesh training segment is valid Chrome trace JSON containing
    engine lifecycle spans, a collective span, and a compile span."""
    import paddle_tpu.distributed as dist
    import paddle_tpu.nn as nn
    from paddle_tpu.inference import ContinuousBatchingEngine

    # --- serving half: lifecycle events + dispatch spans
    rng = np.random.default_rng(0)
    eng = ContinuousBatchingEngine(serving_gpt, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=4,
                                   prefill_chunk=8, q_block=2)
    for n, new in ((5, 6), (9, 4)):
        eng.add_request(rng.integers(0, 96, (n,)).astype(np.int32), new)
    eng.run()

    # --- training half: 2-rank group, eager DP sync (collective span)
    # + a to_static capture (compile span)
    g = dist.new_group([0, 1])
    net = dist.DataParallel(nn.Linear(8, 8), group=g)
    opt = paddle.optimizer.SGD(parameters=net.parameters())
    x = paddle.to_tensor(np.ones((4, 8), "float32"))
    loss = (net(x) ** 2).mean()
    loss.backward()
    net.apply_collective_grads()
    opt.step()
    opt.clear_grad()

    fresh = nn.Linear(8, 8)

    @paddle.jit.to_static
    def step(inp):
        return (fresh(inp) ** 2).mean()

    step(x)

    path = tracing.export_trace(str(tmp_path / "trace.json"))
    assert path and os.path.exists(path)
    rec = json.load(open(path))
    evs = rec["traceEvents"]
    names = {e["name"] for e in evs}
    assert {"serving.enqueued", "serving.admitted",
            "serving.prefill_chunk", "serving.first_token",
            "serving.retired"} <= names
    spans = {e["name"] for e in evs
             if e.get("cat") == "span" and e["ph"] == "X"}
    assert "serving.dispatch" in spans       # engine dispatch spans
    assert "collective.psum_mean" in spans   # DP grad-sync collective
    assert "dp.grad_sync" in spans
    assert "compile" in spans                # jit capture
    # every complete event has non-negative duration and a track
    tids = {e["tid"]: e for e in evs if e["ph"] == "M"
            and e["name"] == "thread_name"}
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0 and e["tid"] in tids
    # slot tracks exist (one track per engine slot)
    track_names = {e["args"]["name"] for e in tids.values()}
    assert any(t.startswith("engine/slot") for t in track_names)


# ==========================================================================
# rpc context propagation
# ==========================================================================

def _remote_probe(x):
    """Runs on the rpc server thread; its span must join the trace."""
    with tracing.span("remote_work"):
        return x + 1


def test_rpc_trace_propagation_roundtrip(fresh_trace):
    from paddle_tpu.distributed import rpc

    rpc.init_rpc("worker0", rank=0, world_size=1)
    try:
        with tracing.span("client_op"):
            assert rpc.rpc_sync("worker0", _remote_probe,
                                args=(41,)) == 42
            # async variant: the context is captured on THE CALLING
            # thread before the worker thread spawns — its rpc.client
            # span must join this trace, not start a new root
            root_ctx = tracing.inject()
            fut = rpc.rpc_async("worker0", _remote_probe, args=(1,))
            assert fut.wait() == 2
    finally:
        rpc.shutdown()
    async_begs = [e for e in obs.tail() if e["kind"] == "span.begin"
                  and e["name"] == "rpc.client"]
    assert len(async_begs) == 2
    assert async_begs[1]["trace_id"] == root_ctx["trace_id"]
    assert async_begs[1]["parent_id"] == root_ctx["span_id"]
    begs = {e["name"]: e for e in obs.tail()
            if e["kind"] == "span.begin"}
    assert {"client_op", "rpc.client", "rpc.server",
            "remote_work"} <= set(begs)
    root = begs["client_op"]
    # ONE trace end to end; parent chain crosses the wire
    for name in ("rpc.client", "rpc.server", "remote_work"):
        assert begs[name]["trace_id"] == root["trace_id"], name
    assert begs["rpc.client"]["parent_id"] == root["span_id"]
    assert begs["rpc.server"]["parent_id"] == \
        begs["rpc.client"]["span_id"]
    assert begs["remote_work"]["parent_id"] == \
        begs["rpc.server"]["span_id"]
    assert begs["rpc.server"]["fn"] == "_remote_probe"


# ==========================================================================
# compile spans, retrace causes, HBM gauges
# ==========================================================================

def test_compile_span_retrace_cause_and_hbm_gauges(fresh_trace):
    import jax

    import paddle_tpu.nn as nn

    reg = obs.registry()
    h0 = reg.histogram("train.compile_ms").count
    net = nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(parameters=net.parameters())

    @paddle.jit.to_static
    def step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = paddle.to_tensor(np.ones((2, 4), "float32"))
    step(x)
    # the capture emitted a compile span with geometry attrs and fed
    # the train.compile_ms histogram
    begs = [e for e in obs.tail() if e["kind"] == "span.begin"
            and e["name"] == "compile"]
    assert begs and begs[-1]["fn"] == "step"
    assert begs[-1]["n_inputs"] >= 1
    assert reg.histogram("train.compile_ms").count == h0 + 1
    # HBM gauges: per-program captured-state bytes + process total
    snap = reg.snapshot()["hbm"]
    assert snap["program_state_bytes"]["fn=step"] > 0
    assert snap["live_bytes"] > 0
    assert snap["live_bytes"] >= snap["program_state_bytes"]["fn=step"]

    exe = step.concrete_program(x)
    assert exe is not None and exe.trace_count == 1
    vals = [t._data for t in [x] + exe.capt_state]

    # identical-signature re-trace (the jit cache-miss / eviction /
    # scan-window class).  jax caches traces by (fun identity, avals),
    # so tracing the SAME pure through a fresh wrapper is exactly the
    # cache-miss event the counter guards against
    jax.make_jaxpr(lambda *v: exe._pure(*v))(*vals)
    retr = [e for e in obs.tail() if e["kind"] == "compile.retrace"]
    assert retr and retr[-1]["count"] == 2
    assert "same signature" in retr[-1]["cause"]

    # changed-shape re-trace names the offending position
    vals2 = [np.ones((6, 4), "float32")] + vals[1:]
    jax.make_jaxpr(exe._pure)(*vals2)
    retr = [e for e in obs.tail() if e["kind"] == "compile.retrace"]
    assert retr[-1]["count"] == 3
    assert "arg0" in retr[-1]["cause"]
    assert "(2, 4)" in retr[-1]["cause"]
    assert "(6, 4)" in retr[-1]["cause"]


# ==========================================================================
# fleet aggregation
# ==========================================================================

def _rank_registry(rank, *, steps=8, step_ms=None, straggle=0.0):
    """One simulated rank's registry: step histogram, a counter, an
    overlap gauge, a phase histogram the attribution can pick up."""
    r = Registry()
    h = r.histogram("train.step_ms",
                    buckets=obs.LATENCY_BUCKETS_MS)
    base = step_ms if step_ms is not None else 10.0
    for _ in range(steps):
        h.observe(base + straggle)
    r.counter("train.steps").inc(steps)
    r.gauge("train.overlap_frac").set(0.9 - 0.1 * (straggle > 0))
    hc = r.histogram("train.comm_ms", buckets=obs.LATENCY_BUCKETS_MS)
    for _ in range(steps):
        hc.observe(1.0 + straggle)
    return r


def test_fleet_snapshot_merge_8_ranks_with_straggler(metrics_on,
                                                     tmp_path):
    """The 8-dev-mesh acceptance shape: 8 ranks publish through a real
    TCPStore; rank 5 is slow (its p50 shows it), rank 7 never publishes
    (straggler-timeout -> missing, not a hang); counters sum,
    histograms merge elementwise, gauges stay per-rank."""
    from paddle_tpu.distributed import TCPStore

    store = TCPStore("127.0.0.1", 0, world_size=8, is_master=True)
    try:
        regs = {r: _rank_registry(r, straggle=500.0 if r == 5 else 0.0)
                for r in range(8)}
        for r in range(7):        # rank 7 = dead straggler
            aggregate.publish_snapshot(store, r, regs[r])
        t0 = __import__("time").monotonic()
        view = aggregate.fleet_snapshot(
            store=store, world_size=8, rank=0, registry=regs[0],
            timeout=0.2)
        assert __import__("time").monotonic() - t0 < 5.0  # no hang
    finally:
        store.close()
    assert view["missing"] == [7]
    assert view["ranks"] == list(range(7))
    assert view["world_size"] == 8
    # counters sum over the 7 present ranks
    assert view["merged"]["train"]["steps"] == 7 * 8
    # histogram merged elementwise: count is the fleet total and the
    # bucket counts sum to it
    h = view["merged"]["train"]["step_ms"]
    assert h["count"] == 7 * 8
    assert sum(h["counts"]) == h["count"]
    assert h["sum"] == pytest.approx(6 * 8 * 10.0 + 8 * 510.0)
    # gauges keep per-rank identity
    of = view["merged"]["train"]["overlap_frac"]
    assert set(of) == {f"rank={r}" for r in range(7)}
    assert of["rank=5"] == pytest.approx(0.8)
    # skew: the slow rank is attributed, with a positive p50 spread
    skew = view["skew"]
    assert skew["slowest_rank"] == 5
    assert set(skew["p50_ms"]) == set(range(7))
    assert skew["p50_ms"][5] > skew["p50_ms"][0]
    assert skew["p50_spread_ms"] > 0
    assert skew["overlap_frac"][5] == pytest.approx(0.8)
    # phase attribution: rank 5's comm_ms sits far above fleet median
    assert skew["slowest_phase"] == "train.comm_ms"


def test_fleet_snapshot_local_degenerate(metrics_on):
    """No store: the local single-rank view, same shape."""
    reg = _rank_registry(0)
    view = aggregate.fleet_snapshot(registry=reg, rank=0)
    assert view["world_size"] == 1 and view["missing"] == []
    assert view["merged"]["train"]["steps"] == 8
    assert view["skew"]["slowest_rank"] == 0
    assert view["schema_version"] == obs.events.SCHEMA_VERSION


def test_skew_phase_attribution_two_ranks(metrics_on):
    """2-rank regression: the phase reference must exclude the slowest
    rank's own value — with it included, a 2-rank fleet's median IS its
    max, every ratio caps at 1.0 and attribution degenerates to
    declaration order instead of the actual outlier phase."""
    def payload(comm, opt):
        mts = []
        for name, mean in (("train.step_ms", 100.0 + comm),
                           ("train.comm_ms", comm),
                           ("train.opt_step_ms", opt)):
            mts.append({"name": name, "kind": "histogram",
                        "labels": [], "count": 4, "sum": mean * 4,
                        "buckets": list(obs.LATENCY_BUCKETS_MS),
                        "counts": [0] * 9 + [4] + [0] * 18})
        return {"metrics": mts}

    skew = aggregate.derive_skew({0: payload(1.0, 5.0),
                                  1: payload(10.0, 5.0)})
    assert skew["slowest_rank"] == 1
    # comm is 10x the peer; opt is equal — comm must win, not the
    # first _PHASE_HISTS entry
    assert skew["slowest_phase"] == "train.comm_ms"


def test_merge_rejects_mismatched_buckets(metrics_on):
    a = {"metrics": [{"name": "h", "kind": "histogram", "labels": [],
                      "count": 1, "sum": 1.0, "buckets": [1.0, 2.0],
                      "counts": [1, 0, 0]}]}
    b = {"metrics": [{"name": "h", "kind": "histogram", "labels": [],
                      "count": 1, "sum": 1.0, "buckets": [1.0, 3.0],
                      "counts": [1, 0, 0]}]}
    with pytest.raises(ValueError, match="buckets"):
        aggregate.merge_snapshots({0: a, 1: b})


# ==========================================================================
# flight-dump schema v2
# ==========================================================================

def test_flight_dump_schema_v2(tmp_path, metrics_on, monkeypatch):
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    obs.events.clear()
    obs.emit("k", x=1)
    path = obs.dump("schema_check")
    rec = json.load(open(path))
    assert rec["schema_version"] == obs.events.SCHEMA_VERSION == 2
    assert rec["rank"] == 0                  # PADDLE_TRAINER_ID unset
    assert isinstance(rec["host"], str) and rec["host"]
    # rank follows the launcher env (the multi-rank merge key)
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    rec2 = json.load(open(obs.dump("schema_check_rank")))
    assert rec2["rank"] == 3
    assert obs.last_dump().endswith(os.path.basename(obs.last_dump()))


# ==========================================================================
# metrics-off: everything is a cheap no-op
# ==========================================================================

def test_metrics_off_tracing_and_aggregation_noop(tmp_path):
    old = paddle.get_flags("metrics")["metrics"]
    try:
        paddle.set_flags({"metrics": True})
        obs.events.clear()
        tracing._reset()
        paddle.set_flags({"metrics": False})
        with tracing.span("off", a=1):
            assert tracing.inject() is None
            assert tracing.context_fields() == {}
        assert obs.tail() == []                      # nothing emitted

        @tracing.traced
        def f():
            return 1

        assert f() == 1 and obs.tail() == []
        p = str(tmp_path / "t.json")
        assert tracing.export_trace(p) is None       # no stray files
        assert not os.path.exists(p)
        assert aggregate.fleet_snapshot() == {}

        class _Boom:                                  # store untouched
            def set(self, *a, **k):
                raise AssertionError("store touched with metrics off")
            get = add = set

        assert aggregate.fleet_snapshot(store=_Boom(), world_size=8,
                                        rank=0) == {}
    finally:
        paddle.set_flags({"metrics": old})
        tracing._reset()


# ==========================================================================
# the window's timeline (ISSUE 40): call log, read log, collector pauses
# ==========================================================================

from paddle_tpu.observability import steptimer  # noqa: E402


@pytest.fixture
def fresh_logs(fresh_trace):
    steptimer._reset_logs()
    yield
    steptimer._reset_logs()


def _tiny_step():
    w = paddle.to_tensor(np.ones((4, 4), "float32"))

    @paddle.jit.to_static
    def logged_step(x):
        return (x @ w).sum()

    return logged_step, paddle.to_tensor(np.ones((4, 4), "float32"))


def _spans(name):
    return [e for e in obs.tail()
            if e["kind"] == "span.begin" and e["name"] == name]


def test_compiled_calls_write_rows_and_the_eager_first_call_none(fresh_logs):
    step, x = _tiny_step()
    step(x)                                 # eager: discovery
    assert len(steptimer.call_log()) == 0
    assert _spans("to_static.call") == []
    for _ in range(5):
        step(x)
    log = steptimer.call_log()
    assert len(log) == 5 and list(log["n"]) == [1, 2, 3, 4, 5]
    names = steptimer.call_fn_names()
    assert {names[i] for i in log["fn"]} == {"logged_step"}
    marks = np.stack([log[f] for f in steptimer.CALL_FIELDS[2:]], axis=1)
    assert (np.diff(marks, axis=1) > 0).all()       # five ordered marks
    assert (log["enter"][1:] > log["done"][:-1]).all()
    # a row and its span pair by number, not by position
    assert [e["n"] for e in _spans("to_static.call")] == [1, 2, 3, 4, 5]
    assert len(_spans("to_static.launch")) == 5


def test_two_programs_of_one_name_keep_their_rows_apart(fresh_logs):
    (one, x), (two, _) = _tiny_step(), _tiny_step()
    one(x), two(x)                          # eager
    one(x), two(x), two(x), one(x)
    log, names = steptimer.call_log(), steptimer.call_fn_names()
    a, b = int(log["fn"][0]), int(log["fn"][1])
    assert a != b and names[a] == names[b] == "logged_step"
    assert list(log["fn"]) == [a, b, b, a]


def test_a_blocking_read_is_one_row_and_one_span(fresh_logs):
    t = paddle.to_tensor(np.float32(2.5))
    assert len(steptimer.read_log()) == 0
    assert float(t) == 2.5
    (row,) = steptimer.read_log()
    assert row["end"] > row["begin"] > 0
    assert row["utime_ns"] > 0 and row["nivcsw"] >= 0 and row["majflt"] >= 0
    assert len(_spans("tensor.readback")) == 1
    ends = [e for e in obs.tail() if e["kind"] == "span.end"]
    assert ends[-1]["name"] == "tensor.readback"
    # each of the eight host-interop methods is one read
    v = paddle.to_tensor(np.arange(3))
    s = paddle.to_tensor(np.int32(1))
    v.numpy(), v.tolist(), np.asarray(v), s.item(), bool(s), int(s), [7, 8][s]
    assert len(steptimer.read_log()) == 8
    assert len(_spans("tensor.readback")) == 8
    assert list(np.asarray(v, dtype="float32")) == [0.0, 1.0, 2.0]


def test_metrics_off_writes_no_row(fresh_logs):
    step, x = _tiny_step()
    step(x), step(x)
    assert len(steptimer.call_log()) == 1
    hooked = gc.callbacks.count(steptimer._gc_hook)
    paddle.set_flags({"metrics": False})
    off = time.perf_counter_ns()
    try:
        for _ in range(3):
            out = step(x)
        assert float(out) == 64.0
        gc.collect()
        for log in (steptimer.call_log, steptimer.read_log,
                    steptimer.gc_log):
            assert len(log()) == 0              # []-like while off
    finally:
        on = time.perf_counter_ns()
        paddle.set_flags({"metrics": True})
    # nothing was written while it was off (a young collection that
    # took a millisecond before or after may stand in the third ring)
    assert list(steptimer.call_log()["n"]) == [1]
    assert len(steptimer.read_log()) == 0
    pauses = steptimer.gc_log()
    assert not ((pauses["begin"] > off) & (pauses["begin"] < on)).any()
    assert gc.callbacks.count(steptimer._gc_hook) == hooked


@pytest.mark.parametrize("ring,size", [("calls", 4096), ("reads", 4096),
                                       ("gcs", 1024)])
def test_the_rings_wrap_at_their_size(fresh_logs, ring, size):
    assert (steptimer.CALL_RING, steptimer.READ_RING,
            steptimer.GC_RING) == (4096, 4096, 1024)
    extra = 10
    if ring == "calls":
        for _ in range(size + extra):
            n = next(steptimer._calls.numbers)
            steptimer._calls.put(n, 0, n, n + 1, n + 2, n + 3, n + 4)
        log, key = steptimer.call_log(), "n"
    elif ring == "reads":
        for k in range(size + extra):
            steptimer.note_read(k + 1, k + 2)
        log, key = steptimer.read_log(), "seq"
    else:
        obs.events.set_capacity(8)      # generation-2 pauses are spans
        gc.disable()                    # no real collection in between
        try:
            for _ in range(size + extra):
                steptimer._gc_hook("start", {"generation": 2})
                steptimer._gc_hook("stop", {"generation": 2, "collected": 3,
                                            "uncollectable": 0})
            log, key = steptimer.gc_log(), "seq"
        finally:
            gc.enable()
            obs.events.set_capacity(512)
        assert (log["generation"] == 2).all() and (log["collected"] == 3).all()
    assert len(log) == size
    assert list(log[key][[0, -1]]) == [extra + 1, size + extra]
    assert (np.diff(log[key]) == 1).all()           # in time order


def test_the_collector_hook_is_installed_once_and_logs_long_pauses(
        fresh_logs):
    step, x = _tiny_step()
    for _ in range(4):
        step(x)
    assert gc.callbacks.count(steptimer._gc_hook) == 1

    def logged(generation, collected=None):
        # other young collections may pause a loaded host a millisecond
        # too: look for the ones made here
        log = steptimer.gc_log()
        keep = log["generation"] == generation
        if collected is not None:
            keep &= log["collected"] == collected
        return log[keep]

    # a young collection under a millisecond: two clock reads, no row
    steptimer._gc_hook("start", {"generation": 0})
    steptimer._gc_hook("stop", {"generation": 0, "collected": 77077,
                                "uncollectable": 0})
    assert len(logged(0, 77077)) == 0 and _spans("host.gc") == []
    # ... and one that paused the host a millisecond is logged, no span
    steptimer._gc_hook("start", {"generation": 1})
    steptimer._gc_t0 -= steptimer.GC_LOG_NS
    steptimer._gc_hook("stop", {"generation": 1, "collected": 77077,
                                "uncollectable": 0})
    (row,) = logged(1, 77077)
    assert row["end"] - row["begin"] >= steptimer.GC_LOG_NS
    assert _spans("host.gc") == []
    gc.collect()                            # generation 2: row and span
    (row,) = logged(2)
    assert row["end"] > row["begin"]
    (span,) = _spans("host.gc")
    assert span["generation"] == 2
    step(x)
    assert gc.callbacks.count(steptimer._gc_hook) == 1


def test_log_and_device_trace_share_a_clock_by_n(fresh_logs, tmp_path):
    """With a profiler session live the marks are spans in the
    ``.xplane.pb``: ``to_static.call`` with its ``n``, ``tensor.readback``
    and ``host.gc``; rows and spans pair by ``n`` to one offset."""
    import glob
    import sys

    import jax
    from jax.profiler import ProfileData
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from perf import window_log
    step, x = _tiny_step()
    step(x), step(x)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(4):
            out = step(x)
        float(out)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    traced = window_log.traced_calls(path)
    assert sorted(traced) == [2, 3, 4, 5]
    offset, spread_us, pairs = window_log.clock_offset(
        steptimer.call_log(), traced)
    assert pairs == 4 and spread_us < 200
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert {"to_static.call", "tensor.readback", "host.gc"} <= names
    # the read's row lands inside its span once moved to the trace's clock
    (read,) = steptimer.read_log()
    spans = [(ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name == "tensor.readback"]
    lo, hi = read["begin"] + offset, read["end"] + offset
    assert spans[0][0] - 2e5 <= lo and hi <= spans[0][1] + 2e5
