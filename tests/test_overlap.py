"""Overlap-scheduled hybrid-parallel training (ISSUE 11).

Gates:
- overlap-scheduled bucketed DP grad sync is BITWISE identical to the
  serialized ``apply_collective_grads`` on a CPU mesh (per-param AND
  fused-flat-grad paths, jax.shard_map fallback included);
- bucket readiness follows the backward walk (last layers first);
- ``no_sync`` pauses the scheduler (gradient accumulation);
- comm_ms / overlap_frac accounting reaches the observability registry;
- the pipeline's pp_overlap_p2p reorder changes the schedule, not the
  values.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.nn as nn
from paddle_tpu.core import state as _state


def _net():
    paddle.seed(0)
    return nn.Sequential(nn.Linear(8, 32), nn.GELU(),
                         nn.Linear(32, 32), nn.GELU(),
                         nn.Linear(32, 4))


def _x(seed=0):
    return paddle.to_tensor(np.random.default_rng(seed).normal(
        size=(16, 8)).astype("float32"))


def _grads(dp):
    return [np.asarray(p.grad._read()) for p in dp.parameters()
            if p.grad is not None]


def _run_sync(overlap, bucket_bytes=None, steps=1):
    dp = dist.DataParallel(_net(), overlap_grad_sync=overlap)
    if overlap and bucket_bytes is not None:
        dp._overlap.bucket_bytes = bucket_bytes
    x = _x()
    for _ in range(steps):
        loss = (dp(x) ** 2).mean()
        loss.backward()
        dp.apply_collective_grads()
    return _grads(dp), dp


def test_overlap_bitwise_vs_serialized_per_param():
    """Tiny bucket cap -> one collective per param, dispatched during
    backward; result must be bit-identical to the serialized sync."""
    ref, _ = _run_sync(False)
    got, dp = _run_sync(True, bucket_bytes=1)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)
    assert dp._last_sync_collectives == 6  # 3 Linears x (w, b)
    acct = dp._overlap.last
    assert acct["buckets"] == 6 and acct["comm_ms"] > 0
    assert 0.0 <= acct["overlap_frac"] <= 1.0


def test_overlap_bitwise_default_bucket():
    """Default 25MB cap -> one bucket for this tiny net (degenerates to
    the serialized schedule, still bitwise)."""
    ref, _ = _run_sync(False)
    got, dp = _run_sync(True)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)
    assert dp._last_sync_collectives == 1


def test_ready_order_is_backward_walk():
    """Bucket readiness = the order the backward walk finalizes grads:
    the LAST layer's params become ready first (the EagerReducer
    reverse-order rationale)."""
    _, dp = _run_sync(True, bucket_bytes=1)
    order = dp._overlap.last["ready_order"]
    params = [p for p in dp._layers.parameters() if not p.stop_gradient]
    assert sorted(order) == list(range(len(params)))
    # the first finalized param belongs to the last Linear, the final
    # finalized param to the first Linear
    assert order[0] in (len(params) - 2, len(params) - 1)
    assert order[-1] in (0, 1)


def test_overlap_bitwise_with_fused_optimizer():
    """Grads living in the fused optimizer's flat buckets (views):
    overlap sync must stay bitwise vs serialized, and the optimizer
    must keep stepping (parity of the trained weights)."""
    import paddle_tpu.optimizer as opt

    def train(overlap):
        dp = dist.DataParallel(_net(), overlap_grad_sync=overlap)
        if overlap:
            dp._overlap.bucket_bytes = 1
        o = opt.AdamW(learning_rate=1e-2, parameters=dp.parameters())
        x = _x(1)
        for _ in range(3):
            loss = (dp(x) ** 2).mean()
            loss.backward()
            dp.apply_collective_grads()
            o.step()
            o.clear_grad(set_to_zero=True)
        return [np.asarray(p._read()) for p in dp.parameters()]

    ref = train(False)
    got = train(True)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)


def test_no_sync_pauses_scheduler():
    """Accumulation micro-steps under no_sync must not dispatch bucket
    collectives; the sync after the scope covers the accumulated grad
    and stays bitwise vs the serialized accumulate-then-sync."""
    def run(overlap):
        dp = dist.DataParallel(_net(), overlap_grad_sync=overlap)
        if overlap:
            dp._overlap.bucket_bytes = 1
        with dp.no_sync():
            ((dp(_x(2)) ** 2).mean()).backward()
            if overlap:
                assert not dp._overlap._pending \
                    and not dp._overlap._ready_ids
        ((dp(_x(3)) ** 2).mean()).backward()   # accumulates
        dp.apply_collective_grads()
        return _grads(dp)

    ref = run(False)
    got = run(True)
    for a, b in zip(ref, got):
        assert np.array_equal(a, b)


def test_overlap_metrics_reach_registry():
    from paddle_tpu.observability import metrics as m
    reg = m.registry()
    before = reg.counter("train.bucket_syncs",
                         "bucketed grad-sync collectives issued").value
    _, dp = _run_sync(True, bucket_bytes=1)
    assert reg.counter("train.bucket_syncs", "").value == before + 6
    assert reg.gauge("train.overlap_frac", "").value is not None
    snap = reg.snapshot()
    assert "train" in snap and "comm_ms" in snap["train"]


def test_overlap_flag_default_off():
    dp = dist.DataParallel(_net())
    assert dp._overlap is None  # serialized path untouched by default
    assert _state.get_flag("dp_overlap_grad_sync") is False


# ----------------------------------------------------------- pipeline --
def test_pipeline_p2p_overlap_bitwise(tmp_path):
    """pp_overlap_p2p reorders sends, never values: 1F1B loss and every
    stacked-leaf grad bitwise across the flag."""
    import paddle_tpu.nn.functional as F
    from paddle_tpu.distributed.fleet.pipeline import PipelinedBlocks

    mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["pp", "dp"])

    class Block(nn.Layer):
        def __init__(self, width=16):
            super().__init__()
            self.fc1 = nn.Linear(width, 2 * width)
            self.fc2 = nn.Linear(2 * width, width)

        def forward(self, x):
            return x + self.fc2(F.gelu(self.fc1(x)))

    rng = np.random.default_rng(4)
    x = rng.normal(size=(8, 4, 16)).astype("float32")
    y = rng.normal(size=(8, 4, 16)).astype("float32")

    def loss_fn(out, tgt):
        return ((out - tgt) ** 2).mean()

    def run(flag):
        old = _state.get_flag("pp_overlap_p2p")
        _state.set_flags({"pp_overlap_p2p": flag})
        try:
            paddle.seed(5)
            pipe = PipelinedBlocks(Block, 4, mesh=mesh, pp_axis="pp",
                                   num_microbatches=4)
            loss = pipe.train_batch(paddle.to_tensor(x),
                                    paddle.to_tensor(y), loss_fn,
                                    batch_axes="dp")
            loss.backward()
            grads = [np.asarray(pipe.stacked_parameter(n).grad._read())
                     for n, _ in pipe.template.named_parameters()]
            return float(loss), grads
        finally:
            _state.set_flags({"pp_overlap_p2p": old})

    l_on, g_on = run(True)
    l_off, g_off = run(False)
    assert l_on == l_off
    for a, b in zip(g_on, g_off):
        assert np.array_equal(a, b)


# ----------------------------------------------------------- topology --
def test_topology_process_mesh_bridge():
    from paddle_tpu.distributed.fleet.topology import \
        HybridCommunicateGroup

    hcg = HybridCommunicateGroup(dp_degree=2, pp_degree=2, mp_degree=2)
    mesh = hcg.process_mesh()
    assert mesh.dim_names == ["dp", "pp", "mp"]
    assert mesh.shape == [2, 2, 2]
    # degenerate axes are dropped; explicit selection keeps order
    mesh2 = HybridCommunicateGroup(dp_degree=4,
                                   pp_degree=2).process_mesh()
    assert mesh2.dim_names == ["dp", "pp"]
    g = hcg.get_data_parallel_comm_group()
    assert g.nranks == 2 and g.ranks == [0, 4]


def test_dryrun_multichip_pipeline():
    """One of the five layouts of ``__graft_entry__.dryrun_multichip(8)``
    (tests/test_models.py holds it to them): GPipe over pp x dp, the
    activation hops whose overlap the cases above measure."""
    import __graft_entry__ as g
    g._force_virtual_cpu(8)
    g._dryrun_pipeline(8)
