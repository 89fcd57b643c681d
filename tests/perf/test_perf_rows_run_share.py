"""``expert_rows_run_share.train``: its entry, its reader on a program
with the ``moe.slot_rows_run_share`` gauge and on one without."""
import numpy as np
import pytest
import perf_testlib as L

from perf import loader
from perf.drivers import common

METRIC = "expert_rows_run_share.train"
CELL = "lfm2-24b-a2b.pretrain_8k"


def _read(run):
    return loader.module("metrics", METRIC).read(run)


def test_the_entry_is_for_the_cells_with_a_sparse_block():
    per_layer = loader.benchmark()["per_layer"]
    entry = dict(loader.by_name(per_layer, METRIC, "metric"))
    cells = entry.pop("workloads")
    assert entry == {
        "name": METRIC, "unit": "ratio", "better": "lower",
        "source": "program_counter", "moves": "train_tokens_per_s",
        "layer": loader.by_name(per_layer, "expert_dispatch_device_ms.train",
                                "metric")["layer"]}
    # the cell it was written for stays first; a later cell may follow
    assert cells[0] == CELL and set(L.SPARSE_CELLS) <= set(cells)


@pytest.mark.parametrize("snapshot", [
    {}, {"moe": {"routed_here_share": {"layer=layer_1": 0.2}}},
    {"moe": {"slot_rows_run_share": {"layer=layer_1": None}}}])
def test_a_program_without_the_gauge_gives_nothing(snapshot, monkeypatch):
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(metrics, "snapshot", lambda: snapshot)
    run = common.Run(None)
    assert _read(run) is None and run.notes == []


def test_the_mean_over_the_layers_of_what_the_program_counted(monkeypatch):
    import paddle_tpu as paddle
    from paddle_tpu.incubate.distributed.models import moe
    monkeypatch.setattr(moe, "_SLOTS_AT_A_TIME", 8)
    # the blocks built here leave no ring and no gauge behind for other
    # files' tests
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(moe, "_calls_of", {})
    monkeypatch.setattr(metrics.registry(), "_metrics",
                        dict(metrics.registry()._metrics))
    x = paddle.to_tensor(
        np.random.default_rng(0).standard_normal((24, 16)).astype("f4"))
    want = {}
    for name, held in (("rows_run_a", 2), ("rows_run_b", 6)):
        block = moe.SparseMoEBlock(16, 8, 8, 2, expert_offset=1,
                                   experts_held=held, name=name)
        for _ in range(2):
            _, tally, chunks = block(x)
            block.count(tally, chunks)
        routed = int(np.asarray(tally._read())[:-1].sum())
        want[name] = -(-routed // 8) / 6        # 48 slots: 6 chunks of 8
    assert 0 < want["rows_run_a"] < want["rows_run_b"] <= 1
    snap = metrics.snapshot()
    monkeypatch.setattr(metrics, "snapshot", lambda: {"moe": {
        "slot_rows_run_share": {
            k: v for k, v in snap["moe"]["slot_rows_run_share"].items()
            if "rows_run_" in k}}})
    run = common.Run(None)
    assert _read(run) == pytest.approx(sum(want.values()) / 2)
    assert '"rows_run_b": ' in run.notes[0]
