"""``step_mfu.train``, the whole step's share of the chip's peak: its
entry, its arithmetic by hand on a synthetic trace of two steps, and
what it does where there is nothing to read or too much."""
import pytest
import perf_testlib as L  # noqa: F401  (puts the checkout on sys.path)

from perf import loader
from perf import phase_reduce as pr
from perf import trace_reduce as tr
from perf.drivers import common

METRIC = "step_mfu.train"
FLOPS_A_TOKEN = 1.0e6


class _Models:
    @staticmethod
    def train_flops_per_token(cfg, batch):
        # the adapter's count is the reader's numerator, whatever it holds
        assert cfg == {"family": "toy"} and batch["seq_len"] == 100
        return FLOPS_A_TOKEN


class _Ctx:
    trace_dir = "unused"
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    traffic = {"batch": {"rows": 3, "seq_len": 100}}
    cfg = {"family": "toy"}
    models = _Models


def _read(run):
    return loader.module("metrics", METRIC).read(run)


def _run(monkeypatch, busy_ns=400_000, gap_ns=100_000, calls=2, chips=1):
    """Two steps traced: each ``busy_ns`` of device operations, then
    ``gap_ns`` in which the device waits for the host."""
    step = busy_ns + gap_ns
    events = [[f"%fusion.{i} f32[8]", 1000 + i * step, busy_ns]
              for i in range(2)]
    host = [["train_step", 1000 + i * step, step] for i in range(2)]
    host += [["to_static.call", 1010 + i * step, 100] for i in range(calls)]
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{
            "name": "XLA Ops", "events": events,
            "op_names": ["jit(train_step)/optimizer/mul"] * 2}]},
        {"name": "/host:CPU", "lines": [{"name": "python3",
                                         "events": host}]}]}
    ctx = _Ctx()
    ctx.devices = ["chip"] * chips
    run = common.Run(ctx)
    run.trace = tr.Trace({"planes": [
        {"name": p["name"], "lines": [{"name": ln["name"],
                                       "events": ln["events"]}
                                      for ln in p["lines"]]}
        for p in raw["planes"]]})
    monkeypatch.setattr(tr, "find_xplane", lambda d: "synthetic")
    monkeypatch.setattr(pr, "load", lambda path: raw)
    return run


def test_the_entry_lists_every_cell_the_training_loop_drives():
    bench = loader.benchmark()
    entry = dict(loader.by_name(bench["per_layer"], METRIC, "metric"))
    cells = entry.pop("workloads")
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "train_tokens_per_s"}
    assert "mfu" in METRIC.split(".")[0].split("_")
    trained = [w["name"] for w in bench["workloads"] if loader.data(
        "traffic", w["traffic"])["driver"] == "train_loop"]
    assert len(trained) >= 4 and set(trained) <= set(cells)
    # one share of the whole step, and it is a metric: the untraced
    # run's note under a name without ``mfu`` left with it
    with open(loader.module("drivers", "train_loop").__file__) as f:
        assert "share_of_peak" not in f.read()


def test_two_steps_at_a_known_busy_time_and_idle_gap_by_hand(monkeypatch):
    run = _run(monkeypatch)
    # 2 steps x 300 tokens x 1e6 operations in 2 x (400 + 100) us, busy
    # AND idle, of a chip that does 1e12 a second: 6e8 of 1e9
    assert _read(run) == pytest.approx(60.0)
    assert any('"step_mfu_steps": 2' in n for n in run.notes)
    # the idle gap counts: the same work with no gap reads higher by
    # (400 + 100) / 400, and four chips could do four times as much
    assert _read(_run(monkeypatch, gap_ns=0)) == pytest.approx(75.0)
    assert _read(_run(monkeypatch, chips=4)) == pytest.approx(15.0)


def test_nothing_to_read_gives_none(monkeypatch):
    # an untraced run
    untraced = common.Run(_Ctx())
    assert _read(untraced) is None and untraced.notes == []
    # a trace without ``to_static.call`` spans: a program from before
    # the spans, or a loop that calls no compiled step
    run = _run(monkeypatch, calls=0)
    assert _read(run) is None
    assert not any("step_mfu" in n for n in run.notes)


def test_a_share_over_100_raises(monkeypatch):
    # the same operations in a hundredth of the time
    run = _run(monkeypatch, busy_ns=4_000, gap_ns=1_000)
    with pytest.raises(ValueError, match=r"step_mfu.train reads 6000.00%"):
        _read(run)
