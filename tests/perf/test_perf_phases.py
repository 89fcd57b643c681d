"""The phase reduction (perf/phase_reduce.py): op_names out of a
trace's HLO modules, scopes and phases out of op_names, and a stretch
of gpt2-medium.pretrain's trace recorded on the v5e with the program's
phase scopes in it (tests/perf/data/recorded_phase_trace.json)."""
import json
import os

import pytest
from perf_testlib import DATA

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

NEW_METRICS = ("forward_device_ms.train", "recompute_device_ms.train",
               "backward_device_ms.train", "optimizer_device_ms.train",
               "head_loss_device_ms.train",
               "unattributed_device_share.train", "launch_ms.train",
               "state_io_ms.train")
PHASE_METRICS = NEW_METRICS[:6]


# ----------------------------------------------------- by hand
@pytest.mark.parametrize("op_name, names, phase", [
    ("jit(train_step)/GPTForCausalLM/gpt/block_3/checkpoint/attn/qkv/"
     "dot_general",
     ["GPTForCausalLM", "gpt", "block_3", "checkpoint", "attn", "qkv"],
     "forward"),
    ("jit(train_step)/backward/GPTForCausalLM/gpt/block_1/"
     "transpose(jvp(backward))/GPTForCausalLM/gpt/block_1/jvp()/"
     "checkpoint/rematted_computation/ln1/mul",
     ["backward", "GPTForCausalLM", "gpt", "block_1", "backward",
      "GPTForCausalLM", "gpt", "block_1", "checkpoint",
      "rematted_computation", "ln1"], "recompute"),
    ("jit(train_step)/backward/GPTForCausalLM/gpt/block_1/"
     "transpose(jvp(backward))/GPTForCausalLM/gpt/block_1/jvp()/"
     "checkpoint/ln2/reduce_sum",
     ["backward", "GPTForCausalLM", "gpt", "block_1", "backward",
      "GPTForCausalLM", "gpt", "block_1", "checkpoint", "ln2"],
     "backward"),
    # the backward's own linearisation runs the flash forward again
    ("jit(train_step)/backward/GPTForCausalLM/gpt/block_23/jvp(attn)/"
     "flash_attention_fwd/pallas_call",
     ["backward", "GPTForCausalLM", "gpt", "block_23", "attn",
      "flash_attention_fwd"], "recompute"),
    ("jit(train_step)/backward/GPTForCausalLM/gpt/block_23/"
     "convert_element_type",
     ["backward", "GPTForCausalLM", "gpt", "block_23"], "backward"),
    ("jit(train_step)/backward/GPTForCausalLM/lm_head/transpose(jvp())/"
     "dot_general", ["backward", "GPTForCausalLM", "lm_head"], "backward"),
    ("jit(train_step)/backward/GPTForCausalLM/loss/"
     "transpose(jvp(jit(_where)))/select_n",
     ["backward", "GPTForCausalLM", "loss"], "backward"),
    ("jit(train_step)/optimizer/clip/reduce_sum", ["optimizer", "clip"],
     "optimizer"),
    ("jit(train_step)/clear_grad/broadcast_in_dim", ["clear_grad"],
     "optimizer"),
    # JAX's own components are not the program's scopes
    ("jit(pure)/checkpoint/rematted_computation/mul",
     ["checkpoint", "rematted_computation"], "unattributed"),
    ("jit(train_step)/jit(main)/jit(_take)/gather", [], "unattributed"),
    ("jit(train_step)/convert_element_type", [], "unattributed"),
    ("vals[3]", [], "unattributed"),
    ("", [], "unattributed"),
])
def test_scopes_and_phase_of_an_op_name(op_name, names, phase):
    assert pr.scopes(op_name) == names
    assert pr.phase_of_op(op_name) == phase


def test_head_and_loss_are_a_cross_cut():
    fwd = "jit(s)/GPTForCausalLM/lm_head/dot_general"
    bwd = "jit(s)/backward/GPTForCausalLM/loss/transpose(jvp())/mul"
    assert pr.is_head_or_loss(pr.scopes(fwd))
    assert pr.is_head_or_loss(pr.scopes(bwd))
    assert (pr.phase_of_op(fwd), pr.phase_of_op(bwd)) == ("forward",
                                                          "backward")
    assert not pr.is_head_or_loss(["GPTForCausalLM", "gpt", "ln_f"])


def test_shown_path_folds_layers_and_repeats():
    assert pr.shown_path(
        ["backward", "GPTForCausalLM", "gpt", "block_11", "backward",
         "GPTForCausalLM", "gpt", "block_11", "checkpoint",
         "rematted_computation", "ln1"]) == "GPTForCausalLM/gpt/block_*/ln1"
    assert pr.shown_path(["optimizer", "clip"]) == "optimizer/clip"
    assert pr.shown_path([]) == "-"


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _ld(number, payload):
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _computation(cid, instructions):
    """A serialized HloComputationProto whose instructions are (name,
    opcode, op_name or None, ids of the computations it calls)."""
    body = _ld(1, f"computation.{cid}".encode()) + _varint(5 << 3) \
        + _varint(cid)
    for name, opcode, op_name, called in instructions:
        ins = _ld(1, name.encode()) + _ld(2, opcode.encode()) \
            + _varint(5 << 3) + _varint(300)
        if op_name is not None:
            ins += _ld(7, _ld(1, b"mul") + _ld(2, op_name.encode()))
        if called:      # packed, as proto3 writes a repeated int64
            ins += _ld(38, b"".join(_varint(c) for c in called))
        body += _ld(2, ins)
    return _ld(3, body)


def _hlo_proto(*computations):
    return _ld(1, _ld(1, b"jit_train_step") + b"".join(computations))


def test_op_names_are_read_from_the_traces_hlo_modules():
    proto = _hlo_proto(
        _computation(300, [
            ("dot.1", "dot", "jit(train_step)/backward/m/fc/"
             "transpose(jvp())/dot_general", []),
            ("mul.2", "multiply", "jit(train_step)/optimizer/mul", []),
            ("p.0", "parameter", None, [])]),
        _computation(7, [
            ("fusion.812", "fusion", "jit(train_step)/optimizer/mul", [300]),
            ("copy.3", "copy", None, []),
            ("x" * 200, "add",
             "jit(train_step)/backward/loss/" + "y" * 300, [])]))
    meta = _ld(1, _varint(7)) + _ld(2, b"jit_train_step(7)") \
        + _ld(5, _varint(1 << 3) + _varint(1) + _ld(6, proto))
    other = _ld(2, b"no_proto(1)")
    plane = _ld(2, b"/host:metadata") \
        + _ld(4, _varint(1 << 3) + _varint(7) + _ld(2, meta)) \
        + _ld(4, _varint(1 << 3) + _varint(1) + _ld(2, other))
    ignored = _ld(2, b"/host:CPU") + _ld(4, _varint(1 << 3) + _varint(7)
                                         + _ld(2, meta))
    space = _ld(1, ignored) + _ld(1, plane) + _ld(4, b"host")
    protos = pr.hlo_protos(space)
    assert list(protos) == ["jit_train_step(7)"]
    names, fused = pr.op_names_of(protos["jit_train_step(7)"])
    assert names["fusion.812"] == "jit(train_step)/optimizer/mul"
    assert fused == {"fusion.812": [names["dot.1"], names["mul.2"]]}
    assert {pr.phase_of_op(o) for o in fused["fusion.812"]} == {
        "backward", "optimizer"}
    assert "copy.3" not in names
    assert names["x" * 200].endswith("y" * 300)
    assert pr.instruction_name(
        "%fusion.812 = bf16[8,1024]{1,0} fusion(bf16[8] %p)") == "fusion.812"
    assert pr.instruction_name("plain") == "plain"


# ------------------------------------------------ the recorded traces
class _Ctx:
    trace_dir = "unused"


def _run(raw, monkeypatch, window=None):
    """A driver's Run over a recorded trace instead of a file."""
    run = common.Run(_Ctx())
    run.trace = tr.Trace({"planes": [
        {"name": p["name"],
         "lines": [{"name": ln["name"], "events": ln["events"]}
                   for ln in p["lines"]]} for p in raw["planes"]]})
    if window:
        run.trace.lo, run.trace.hi = window
    monkeypatch.setattr(tr, "find_xplane", lambda d: "recorded")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    return run


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "recorded_phase_trace.json")) as f:
        return json.load(f)


def test_recorded_phases_add_up_to_the_busy_time(recorded, monkeypatch):
    run = _run(recorded["raw"], monkeypatch, recorded["window"])
    t = pr.table(run)
    want = recorded["expect"]
    assert t is not None and not t.missing
    assert t.calls == want["calls"] > 0
    assert t.busy_ns == pytest.approx(want["busy_ns"], rel=1e-12)
    parts = {p: t.phase_ns(p) for p in pr.PHASES + (pr.UNATTRIBUTED,)}
    assert parts == pytest.approx(want["phase_ns"], rel=1e-12)
    assert sum(parts.values()) == pytest.approx(t.busy_ns, rel=1e-12)
    assert all(parts[p] > 0 for p in pr.PHASES)
    assert parts[pr.UNATTRIBUTED] <= 0.10 * t.busy_ns
    assert t.head_loss_ns() == pytest.approx(want["head_loss_ns"])
    assert 0 < t.head_loss_ns() < t.busy_ns
    # the readers, through their files: the four phases and the
    # unattributed share make up the device time per call
    values = {m: loader.module("metrics", m).read(run) for m in NEW_METRICS}
    assert all(v is not None and v >= 0 for v in values.values()), values
    per_call = t.busy_ns / t.calls / 1e6
    phases = sum(values[m] for m in NEW_METRICS[:4])
    assert phases + values["unattributed_device_share.train"] / 100 \
        * per_call == pytest.approx(per_call, rel=1e-9)
    assert values["launch_ms.train"] > 0 and values["state_io_ms.train"] > 0
    # one note, with every row of breakdown.device_ops placed
    notes = [json.loads(n) for n in run.notes]
    assert [list(n) for n in notes] == [["phase_table"]]
    table = notes[0]["phase_table"]
    rows = [name for name, _ in run.trace.breakdown()["device_ops"]]
    assert list(table["breakdown_rows"]) == rows
    assert all(table["breakdown_rows"][r] for r in rows)
    assert set(table["phases"]) == set(pr.PHASES) | {pr.UNATTRIBUTED}
    # the optimizer's update is fused into the weight gradients'
    # matmuls: the note says how much backward time holds it
    mixed = table["fusions_counted_under_one_phase_with_others_inside"]
    assert sum(v for k, v in mixed.items()
               if k.startswith("backward with optimizer")) \
        > 5 * table["phases"]["optimizer"]["seconds"]


def test_a_trace_without_scopes_gives_no_phase_number(monkeypatch):
    """PR 25's recorded trace has no op_names: the step was compiled
    before the scopes existed.  Every phase reader returns None, one
    note says why, and the host-span readers find no program span."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        old = json.load(f)["raw"]
    run = _run(old, monkeypatch)
    for m in NEW_METRICS:
        assert loader.module("metrics", m).read(run) is None, m
    assert [list(json.loads(n)) for n in run.notes] == [
        ["phase_scopes_missing"]]
    # the same with op_names present and all empty (a backend that
    # drops the metadata), and with what the parent commit's step
    # carries (my chip run, PR 26): JAX's checkpoint components and the
    # kernels' function names, none of the program's markers
    for op_name in ("", "jit(pure)/checkpoint/rematted_computation/mul",
                    "jit(pure)/jvp(flash_attention_fwd)/pallas_call"):
        other = json.loads(json.dumps(old))
        for plane in other["planes"]:
            for line in plane["lines"]:
                if plane["name"].startswith("/device:"):
                    line["op_names"] = [op_name] * len(line["events"])
        run = _run(other, monkeypatch)
        for m in PHASE_METRICS:
            assert loader.module("metrics", m).read(run) is None, m
        assert [list(json.loads(n)) for n in run.notes] == [
            ["phase_scopes_missing"]]


def test_an_untraced_run_gives_none():
    run = common.Run(_Ctx())
    for m in NEW_METRICS:
        assert loader.module("metrics", m).read(run) is None, m
    assert run.notes == []


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_has_a_reader_and_an_entry(metric):
    assert callable(loader.module("metrics", metric).read)
    bench = loader.benchmark()
    entry = loader.by_name(bench["per_layer"], metric, "metric")
    # the readers read every family's step: the cell they were written
    # on stays first, and every cell the training loop drives is there
    assert entry["workloads"][0] == "gpt2-medium.pretrain"
    trained = {w["name"] for w in bench["workloads"] if loader.data(
        "traffic", w["traffic"])["driver"] == "train_loop"}
    assert len(trained) >= 4 and trained <= set(entry["workloads"])
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["source"] == ("program_span" if metric in NEW_METRICS[6:]
                               else "device_trace")


def test_cut_keeps_what_overlaps_the_window():
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops",
            "events": [["a", 0, 50], ["b", 40, 100], ["c", 200, 5],
                       ["d", 300, 100]],
            "op_names": ["A", "B", "C", "D"]}]},
        {"name": "/host:CPU", "lines": [{
            "name": "python3",
            "events": [["to_static.call", 10, 20],
                       ["to_static.call", 90, 200]]}]}]}
    got = pr.cut(raw, 45, 250, 10)
    dev, host = got["planes"]
    assert dev["lines"][0]["events"] == [["a", 0, 50], ["b", 40, 100]]
    assert dev["lines"][0]["op_names"] == ["A", "B"]
    assert host["lines"][0]["events"] == []
