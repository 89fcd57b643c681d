"""ISSUE 26: the program's one span API and the phase scopes inside a
compiled step.

* a ``to_static`` AdamW step's compiled HLO names its operations by
  layer path, ``backward``, ``optimizer``, ``lm_head``, ``loss`` and a
  remat component, and its module after the user's function;
* eager calls open no scope;
* ``tracing.span`` emits to the ring only under ``PDTPU_METRICS`` and
  ``profiler.RecordEvent`` keeps its ring record;
* the compiled step's host side and one ``engine.step()`` emit their
  spans nested in order.
"""
import collections
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.amp as amp
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.core import scope
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from perf import phase_reduce  # noqa: E402  (the benchmark's reader)


@pytest.fixture
def ring():
    """Metrics on, a clean ring and deterministic span ids."""
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    obs.events.clear()
    tracing._reset()
    yield
    tracing._reset()
    obs.events.clear()
    paddle.set_flags({"metrics": old})


def _tiny_step():
    """A tiny GPT under AMP O2 + AdamW with a clip, recompute on: the
    shape of perf/models/common.TrainProgram's step."""
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_seq_len=16, intermediate_size=64, dropout=0.0,
        recompute=True, recompute_policy="dots_and_kernels_saveable",
        use_flash_attention=False))
    model.train()
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=model.parameters(),
        grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    model, opt = amp.decorate(models=model, optimizers=opt, level="O2",
                              dtype="bfloat16", master_weight=True)

    @paddle.jit.to_static
    def train_step(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = paddle.to_tensor(
        np.random.default_rng(0).integers(0, 128, (2, 16)).astype("int32"))
    return train_step, ids


@pytest.fixture(scope="module")
def compiled_step():
    """(step, ids, compiled HLO text) after the eager first call and
    the compiling second."""
    step, ids = _tiny_step()
    losses = [float(step(ids, ids)) for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[2] < losses[0]
    exe = step.concrete_program(ids, ids)
    vals = [t._data for t in [ids, ids] + exe.capt_state]
    return step, ids, exe.compiled.lower(*vals).compile().as_text()


def _instructions(hlo):
    """[(opcode, op_name or None)] of the text's non-parameter
    instructions."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(ROOT )?%[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m and m.group(2) != "parameter":
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(2), name.group(1) if name else None))
    return out


def test_compiled_step_carries_phase_scopes(compiled_step):
    _, _, hlo = compiled_step
    # the module is named after the user's function, not jit_pure
    assert hlo.startswith("HloModule jit_train_step")
    assert "jit_pure" not in hlo and "jit(pure)" not in hlo
    paths = collections.Counter()
    phases = collections.Counter()
    named = 0
    for _, op_name in _instructions(hlo):
        if op_name is None:
            continue    # the compiler's own (a CPU convert, a bitcast)
        named += 1
        phases[phase_reduce.phase_of_op(op_name)] += 1
        paths["/".join(phase_reduce.scopes(op_name))] += 1
    seen = set(paths)

    def under(*parts):
        want = "/".join(parts)
        return any(p == want or p.startswith(want + "/") or
                   ("/" + want + "/") in ("/" + p + "/") for p in seen)

    # the root layer has no parent: its class names it; below it the
    # keys the parents registered their sublayers under
    for layer in (("gpt", "wte"), ("gpt", "wpe"), ("gpt", "ln_f"),
                  ("gpt", "block_0", "checkpoint", "attn", "qkv"),
                  ("gpt", "block_1", "checkpoint", "mlp", "fc1"),
                  ("gpt", "block_1", "checkpoint", "ln2")):
        assert under("GPTForCausalLM", *layer), layer
    assert under("GPTForCausalLM", "lm_head")
    assert under("GPTForCausalLM", "loss")
    assert under("backward", "GPTForCausalLM", "gpt", "block_0")
    assert under("backward", "GPTForCausalLM", "lm_head")
    assert under("backward", "GPTForCausalLM", "loss")
    assert under("optimizer") and under("optimizer", "clip")
    # jax.checkpoint's own component survives into the backward, so
    # fleet.recompute adds no scope of its own
    assert any(phase_reduce.REMAT in p and p.startswith("backward/")
               for p in seen)
    for phase in phase_reduce.PHASES:
        assert phases[phase] > 0, phases
    # of the instructions the tracer made (those with an op_name),
    # under a tenth lie outside every program scope
    assert phases[phase_reduce.UNATTRIBUTED] < 0.1 * named, phases


def test_no_forward_is_traced_again_by_the_backward(compiled_step):
    """ISSUE 27: under capture the tape linearises each op as it is
    recorded.  No instruction of the compiled step is what the reader
    calls forward work re-run by the backward's own linearisation (a
    ``jvp(...)`` scope with no ``transpose(...)`` under ``backward``):
    the only recompute left is ``jax.checkpoint``'s own."""
    step, ids, hlo = compiled_step
    rerun = [name for _, name in _instructions(hlo) if name
             and phase_reduce.phase_of_op(name) == "recompute"
             and phase_reduce.REMAT not in phase_reduce.scopes(name)]
    assert not rerun, rerun[:5]
    tape = step.concrete_program(ids, ids).tape_nodes
    assert tape.record > 0 and tape.backward == 0

    # the same step run eagerly linearises at backward time only
    eager_step, eager_ids = _tiny_step()
    eager = scope.tape()
    record, backward = eager.record, eager.backward
    assert np.isfinite(float(eager_step.fn(eager_ids, eager_ids)))
    assert eager.record == record
    assert eager.backward - backward == tape.record


def test_tape_counts_on_the_compile_span_and_in_the_registry(ring):
    reg = obs.registry()

    def count(where):
        return reg.counter("train.tape_nodes",
                           labels={"linearised": where}).value

    before = count("record"), count("backward")
    net = paddle.nn.Linear(4, 4)

    @paddle.jit.to_static
    def step(x):
        loss = (net(x) ** 2).mean()
        loss.backward()
        for p in net.parameters():
            p.clear_grad()
        return loss

    step(paddle.to_tensor(np.ones((2, 4), "float32")))
    end = [e for e in obs.tail() if e["kind"] == "span.end"
           and e["name"] == "compile"][-1]
    assert end["tape_nodes_record"] > 0
    assert end["tape_nodes_backward"] == 0
    assert count("record") - before[0] == end["tape_nodes_record"]
    assert count("backward") == before[1]
    # the rendered trace carries them beside the begin's geometry
    args = [e for e in tracing.render_trace()["traceEvents"]
            if e["name"] == "compile"][-1]["args"]
    assert args["tape_nodes_record"] == end["tape_nodes_record"]
    assert args["fn"] == "step"


def test_scope_names_do_not_depend_on_layer_counters():
    """Two models built one after the other get the same scope paths
    (``_full_name``'s process-wide counter is not used)."""
    a = GPTForCausalLM(GPTConfig(vocab_size=32, hidden_size=8,
                                 num_layers=1, num_heads=1,
                                 max_seq_len=8, intermediate_size=16))
    b = GPTForCausalLM(GPTConfig(vocab_size=32, hidden_size=8,
                                 num_layers=1, num_heads=1,
                                 max_seq_len=8, intermediate_size=16))
    assert a.gpt.full_name() != b.gpt.full_name()
    for m in (a, b):
        assert m._scope_name is None            # no parent
        assert m.gpt._scope_name == "gpt"
        assert m.gpt.blocks[0]._scope_name == "block_0"
        assert m.gpt.blocks[0].attn.qkv._scope_name == "qkv"
    seq = paddle.nn.Sequential(paddle.nn.Linear(2, 2), paddle.nn.ReLU())
    assert [l._scope_name for l in seq.children()] == ["0", "1"]


def test_eager_calls_open_no_scope(monkeypatch):
    opened = []
    real = scope._Phase.__enter__

    def counting(self):
        opened.append(self.name)
        return real(self)

    monkeypatch.setattr(scope._Phase, "__enter__", counting)
    net = paddle.nn.Linear(4, 4)
    opt = paddle.optimizer.SGD(parameters=net.parameters())
    x = paddle.to_tensor(np.ones((2, 4), "float32"))

    def step(inp):
        loss = paddle.nn.functional.cross_entropy(
            net(inp), paddle.to_tensor(np.zeros((2,), "int64")))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step(x)
    assert opened == [] and scope.current() is None
    assert scope.phase("optimizer") is scope.phase("backward")  # no-op
    # ... and the same body under capture opens them: not on the eager
    # first call (discovery), only while the program is traced
    static = paddle.jit.to_static(step)
    static(x)
    assert opened
    assert {"Linear", "loss", "backward", "optimizer",
            "clear_grad"} <= set(opened)
    assert "backward/loss" not in opened and "loss" in opened
    n = len(opened)
    static(x)       # the compiled call traces nothing
    step(x)
    assert len(opened) == n


def test_span_ring_gating_and_record_event(ring):
    paddle.set_flags({"metrics": False})
    with tracing.span("off", a=1):
        with profiler.RecordEvent("off_user"):
            pass
    assert obs.tail() == []
    paddle.set_flags({"metrics": True})
    with tracing.span("outer", phase="x"):
        with profiler.RecordEvent("user"):
            pass
    evs = obs.tail()
    assert [(e["kind"], e["name"]) for e in evs] == [
        ("span.begin", "outer"), ("span", "user"), ("span.end", "outer")]
    beg, user, end = evs
    # the schema tests/test_tracing.py's goldens pin
    assert set(beg) == {"seq", "ts", "kind", "name", "span_id",
                        "trace_id", "tname", "phase"}
    assert set(end) == {"seq", "ts", "kind", "name", "span_id",
                        "trace_id", "dur_us"}
    assert (beg["trace_id"], beg["span_id"]) == (1, 2)
    # RecordEvent's record is what it was: one event at close, no
    # part in the trace context
    assert set(user) == {"seq", "ts", "kind", "name", "dur_us"}
    assert isinstance(user["dur_us"], int)


def test_record_event_error_shows(monkeypatch):
    """The one implementation does not swallow a failing
    TraceAnnotation (RecordEvent.begin used to)."""
    import jax

    def boom(*a, **k):
        raise RuntimeError("annotation failed")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    with pytest.raises(RuntimeError, match="annotation failed"):
        profiler.RecordEvent("x").begin()
    with pytest.raises(RuntimeError, match="annotation failed"):
        with tracing.span("x"):
            pass


def test_profiler_host_buffer_fed_by_program_spans(tmp_path):
    """The Profiler's host buffer is filled from the one span
    implementation: the program's own spans land in it beside
    RecordEvent's, and only while a record window is open."""
    p = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU],
                          timer_only=True)
    p.reset()
    with tracing.span("before_window"):
        pass
    with p:
        with tracing.span("program_span", k=1):
            with profiler.RecordEvent("user_span"):
                pass
    with tracing.span("after_window"):
        pass
    assert tracing._host_sink is None
    events = profiler.load_profiler_result(
        p.export(str(tmp_path / "t.json")))["traceEvents"]
    cats = {e["name"]: e["cat"] for e in events}
    assert cats == {"program_span": "span", "user_span": "user"}


def _children(evs, parent_name):
    """(name, dur_us) of the spans directly under the last span named
    ``parent_name``, in begin order, and that span's own duration."""
    begins = {e["span_id"]: e for e in evs if e["kind"] == "span.begin"}
    ends = {e["span_id"]: e for e in evs if e["kind"] == "span.end"}
    parent = [e for e in begins.values() if e["name"] == parent_name][-1]
    kids = [e for e in begins.values()
            if e.get("parent_id") == parent["span_id"]]
    kids.sort(key=lambda e: e["seq"])
    return ([(k["name"], ends[k["span_id"]]["dur_us"]) for k in kids],
            ends[parent["span_id"]]["dur_us"])


def test_to_static_call_spans(compiled_step, ring):
    step, ids, _ = compiled_step
    step(ids, ids)
    evs = obs.tail()
    call = [e for e in evs if e["kind"] == "span.begin"
            and e["name"] == "to_static.call"]
    assert len(call) == 1 and call[0]["fn"] == "train_step"
    kids, total = _children(evs, "to_static.call")
    assert [k for k, _ in kids] == [
        "to_static.read_state", "to_static.launch",
        "to_static.write_state"]
    assert sum(d for _, d in kids) <= total


def test_engine_step_spans(serving_gpt, ring):
    from paddle_tpu.inference import ContinuousBatchingEngine
    rng = np.random.default_rng(0)
    eng = ContinuousBatchingEngine(serving_gpt, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=4,
                                   prefill_chunk=8, q_block=2)
    for n, new in ((5, 6), (9, 4)):
        eng.add_request(rng.integers(0, 96, (n,)).astype(np.int32), new)
    obs.events.clear()
    eng.step()
    kids, total = _children(obs.tail(), "engine.step")
    assert [k for k, _ in kids] == [
        "engine.retire", "engine.sweep", "engine.admit", "engine.stage",
        "serving.dispatch", "engine.readback"]
    assert sum(d for _, d in kids) <= total
    # every later kind of step keeps the order (a decode window has a
    # second stage span for its state reads)
    order = ["engine.retire", "engine.sweep", "engine.admit",
             "engine.stage", "serving.dispatch", "engine.readback"]
    while eng.has_work:
        obs.events.clear()
        eng.step()
        kids, total = _children(obs.tail(), "engine.step")
        names = [k for k, _ in kids]
        assert names[:3] == order[:3]
        ranks = [order.index(k) for k in names]
        assert ranks == sorted(ranks)
        assert sum(d for _, d in kids) <= total
