#!/usr/bin/env python
"""gpt_3d bench row: hybrid DP x TP x PP training over the fleet
topology (ISSUE 11).

The row answers two questions the single-chip gpt124m headline cannot:

1. does the hybrid path SCALE — tokens/sec on the full mesh vs the
   1-device step rate times the device count (target >= 0.9x linear to
   4 chips);
2. is the communication HIDDEN — ``overlap_frac`` from the
   overlap-scheduled bucketed DP grad sync (distributed/overlap.py) and
   the pipeline's eager-issued ppermute sends (pp_overlap_p2p), with
   ``comm_ms`` alongside so a regression shows up as a number, not a
   vibe.

Layout: ``HybridCommunicateGroup(dp, pp, mp)`` -> ``process_mesh()`` ->
``GPTForCausalLMPipe.train_batch`` (fused 1F1B, dp via batch_axes, TP
via the stacked-leaf tp_rules) compiled as ONE jit step. The overlap
telemetry comes from an eager replicated-DP segment over the same
device set — the path the scheduler exists for (in-program GSPMD comm
is XLA-scheduled and unobservable from the host).

CPU smoke (tests/test_overlap.py): tiny config, dp2 x pp2 on the forced
8-device mesh, validates the row's accounting fields and the bitwise
gates; absolute times and the >= 0.9x scaling gate are TPU-only claims.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np


def _log(msg):
    print(msg, file=sys.stderr, flush=True)


def _measure_gpt_3d(cfg, dp=2, pp=2, mp=1, batch_per_dp=2, seq=64,
                    num_microbatches=2, steps=8, warmup=2,
                    overlap_steps=3, lr=1e-4, peak_flops=None):
    """One gpt_3d row. ``cfg``: GPTConfig (dropout must be 0). Batch is
    ``batch_per_dp * dp`` so per-device work is constant as dp grows —
    the weak-scaling convention the linearity gate assumes."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.core import state as _state
    from paddle_tpu.distributed.fleet.topology import \
        HybridCommunicateGroup
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTForCausalLMPipe

    need = dp * pp * mp
    ndev = len(jax.devices())
    if ndev < need:
        raise RuntimeError(f"gpt_3d wants {need} devices, have {ndev}")
    tp_axis = "mp" if mp > 1 else None
    hcg = HybridCommunicateGroup(dp_degree=dp, pp_degree=pp,
                                 mp_degree=mp)
    mesh = hcg.process_mesh()
    batch = batch_per_dp * dp

    # compile accounting baseline: train.compile_ms is process-global
    # (every _Executable.build in the process feeds it — earlier bench
    # rows included), so the row reports the DELTA over its own run
    from paddle_tpu.observability import metrics as _om
    _comp_h = _om.registry().histogram(
        "train.compile_ms",
        "trace+lower wall time of captured programs",
        _om.LATENCY_BUCKETS_MS)
    comp0 = (_comp_h.count, _comp_h.sum)

    paddle.seed(0)
    pipe = GPTForCausalLMPipe(cfg, mesh, pp_axis="pp", dp_axis="dp",
                              num_microbatches=num_microbatches,
                              tp_axis=tp_axis)
    pipe.train()
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=pipe.parameters())

    @paddle.jit.to_static
    def step(ids, labels):
        loss = pipe.train_batch(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    rng = np.random.default_rng(0)

    def batch_fn():
        ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(
            np.int32)
        lab = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(
            np.int32)
        return paddle.to_tensor(ids), paddle.to_tensor(lab)

    for _ in range(warmup):
        loss = step(*batch_fn())
    float(loss)
    # feed train.step_ms the same steps the row times — into a PRIVATE
    # registry (a fit/bench run earlier in the process would pollute
    # the global one's cumulative histogram); the aggregator reads it
    # through fleet_snapshot(registry=...)
    from paddle_tpu.observability import StepTimer
    from paddle_tpu.observability.metrics import Registry as _Registry
    _row_reg = _Registry("gpt_3d_row")
    st = StepTimer(registry=_row_reg)
    st.mark()
    input_s = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        # input_wait_ms column (ISSUE 19): the host-side batch build +
        # staging time inside the step loop — the share an async
        # double-buffered feed (Model.fit train_prefetch) would hide
        # under device compute. This manual loop stages synchronously,
        # so the column is the full stage cost.
        ti = time.perf_counter()
        ids_t, lab_t = batch_fn()
        input_s += time.perf_counter() - ti
        loss = step(ids_t, lab_t)
        st.step(tokens=batch * seq)
    final_loss = float(loss)  # sync
    dt = (time.perf_counter() - t0) / steps
    tok_s = batch * seq / dt
    # static peak of the captured 3D train step (PR16 analyzer gauge,
    # stamped at capture) — the HBM headroom column remat prices out
    static_peak = max(
        (int(getattr(e, "static_peak_bytes", 0) or 0)
         for e in getattr(step, "_cache", {}).values()), default=0)

    # --- 1-device baseline at the SAME per-device batch (weak scaling)
    paddle.seed(0)
    ref = GPTForCausalLM(cfg)
    ref.train()
    ref_opt = paddle.optimizer.AdamW(learning_rate=lr,
                                     parameters=ref.parameters())

    @paddle.jit.to_static
    def ref_step(ids, labels):
        loss = ref(ids, labels)
        loss.backward()
        ref_opt.step()
        ref_opt.clear_grad()
        return loss

    def ref_batch():
        ids = rng.integers(0, cfg.vocab_size,
                           (batch_per_dp, seq)).astype(np.int32)
        lab = rng.integers(0, cfg.vocab_size,
                           (batch_per_dp, seq)).astype(np.int32)
        return paddle.to_tensor(ids), paddle.to_tensor(lab)

    for _ in range(warmup):
        rl = ref_step(*ref_batch())
    float(rl)
    t0 = time.perf_counter()
    for _ in range(steps):
        rl = ref_step(*ref_batch())
    float(rl)
    dt1 = (time.perf_counter() - t0) / steps
    tok_s_1dev = batch_per_dp * seq / dt1
    chips = dp * pp * mp
    scaling_x = tok_s / (tok_s_1dev * chips) if tok_s_1dev else 0.0

    # --- overlap telemetry: eager replicated-DP segment over the same
    # device set, overlap scheduler ON (the in-program pipeline comm is
    # XLA-scheduled; this is the host-observable half of the claim)
    old_flag = _state.get_flag("dp_overlap_grad_sync")
    _state.set_flags({"dp_overlap_grad_sync": True})
    try:
        paddle.seed(0)
        dp_model = dist.DataParallel(GPTForCausalLM(cfg))
        dp_opt = paddle.optimizer.AdamW(
            learning_rate=lr, parameters=dp_model.parameters())
        ids, lab = batch_fn()
        for _ in range(overlap_steps):
            loss = dp_model(ids, lab)
            loss.backward()
            dp_model.apply_collective_grads()
            dp_opt.step()
            dp_opt.clear_grad()
        ov = dict(dp_model._overlap.last) if dp_model._overlap else {}
        ov.pop("ready_order", None)
        ov["collectives"] = getattr(dp_model, "_last_sync_collectives",
                                    0)
    finally:
        _state.set_flags({"dp_overlap_grad_sync": old_flag})

    # --- fleet columns (ISSUE 12): compile time + per-rank skew from
    # the aggregator.  A single-controller host is one rank, so the
    # local fleet_snapshot over the row's private registry degenerates
    # to {rank: this row's metrics}; multi-host launches pass the
    # launcher's TCP store + world_size and these same columns show the
    # straggler.  compile_ms is the delta of the process-global
    # train.compile_ms over THIS row's captures (step, ref_step,
    # overlap segment).
    from paddle_tpu.observability import aggregate as _agg
    _row_reg.gauge("train.overlap_frac").set(
        float(ov.get("overlap_frac", 0.0)))
    fleet = _agg.fleet_snapshot(registry=_row_reg)
    skew = fleet.get("skew", {}) if fleet else {}
    rank_skew = {
        "step_ms_p50": skew.get("p50_ms", {}),
        "step_ms_spread_ms": skew.get("p50_spread_ms", 0.0),
        "slowest_rank": skew.get("slowest_rank"),
        "slowest_phase": skew.get("slowest_phase"),
        "overlap_frac": skew.get("overlap_frac", {}),
        "ranks_missing": fleet.get("missing", []) if fleet else [],
    }
    comp_cnt = _comp_h.count - comp0[0]
    comp_sum = _comp_h.sum - comp0[1]

    flops_tok = ref.flops_per_token(seq)
    achieved = tok_s * flops_tok
    row = {
        "metric": "gpt_3d_train_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "topology": {"dp": dp, "pp": pp, "mp": mp,
                     "num_microbatches": num_microbatches},
        "chips": chips,
        "batch": batch, "seq_len": seq,
        "step_time_ms": round(dt * 1e3, 2),
        "input_wait_ms": round(input_s / steps * 1e3, 3),
        "static_peak_bytes": static_peak,
        "tokens_per_sec_1dev": round(tok_s_1dev, 1),
        "scaling_x": round(scaling_x, 3),
        "overlap": ov,
        "pp_overlap_p2p": bool(_state.get_flag("pp_overlap_p2p")),
        "compile_ms": {"count": int(comp_cnt),
                       "total": round(float(comp_sum), 1),
                       "mean": round(comp_sum / comp_cnt, 1)
                       if comp_cnt else 0.0},
        "rank_skew": rank_skew,
        "final_loss": round(final_loss, 4),
    }
    if peak_flops:
        row["mfu"] = round(achieved / (peak_flops * chips), 4)
        row["model_tflops_per_sec"] = round(achieved / 1e12, 2)
    return row


def measure_recovery(world=2, num_iters=12, snapshot_every=3,
                     death_at=6):
    """Elastic recovery column (ISSUE 15): time-to-resume after an
    injected ``rank_dead`` plus the buddy-snapshot overhead at cadence
    ``snapshot_every``.  The rig is the host-side FleetSupervisor drill
    (thread ranks over a loopback TCPStore — the same fabric a real
    fleet's detector/snapshot/recovery path runs on; the device only
    executes the train step), so the column measures the recovery
    machinery itself on any platform: heartbeat-expiry detection, the
    coded collective timeout, buddy restore and data fast-forward."""
    import socket
    import threading

    import paddle_tpu as paddle
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.observability import metrics as om
    from paddle_tpu.resilience import FleetSupervisor, faults

    class _Reg(paddle.io.Dataset):
        def __init__(self, n=256):
            rng = np.random.default_rng(0)
            self.x = rng.normal(size=(n, 16)).astype("float32")
            self.y = (self.x @ np.arange(1, 17, dtype="float32")[:, None]
                      ).astype("float32")

        def __len__(self):
            return len(self.x)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    def make_model():
        paddle.seed(0)
        net = paddle.nn.Linear(16, 1)
        m = paddle.Model(net)
        m.prepare(paddle.optimizer.SGD(parameters=net.parameters(),
                                       learning_rate=0.01),
                  paddle.nn.MSELoss())
        return m

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    host = TCPStore("127.0.0.1", port, is_master=True)
    reg = om.registry()
    snap_h = reg.histogram("elastic.snapshot_ms")
    snap0 = (snap_h.count, snap_h.sum)
    data = _Reg()
    models = [make_model() for _ in range(world)]
    sups, results = [], {}
    faults.clear()
    faults.inject("rank_dead", str(world - 1), 1, death_at)
    try:
        for r in range(world):
            sups.append(FleetSupervisor(
                "127.0.0.1", port, f"rank{r}", world,
                is_master=(r == 0), snapshot_every=snapshot_every,
                collective_timeout_ms=2500.0,
                heartbeat_interval=0.25, heartbeat_timeout=2.5,
                recovery_timeout_s=45.0))

        def worker(r):
            results[r] = sups[r].fit(models[r], data, batch_size=4,
                                     num_iters=num_iters, verbose=0)
        t0 = time.perf_counter()
        ts = [threading.Thread(target=worker, args=(r,), daemon=True)
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        wall_s = time.perf_counter() - t0
    finally:
        faults.clear()
        for sup in sups:
            sup.close()
        host.close()
    lr = next((s.last_recovery for s in sups
               if s.last_recovery is not None), None)
    snaps = snap_h.count - snap0[0]
    return {
        "world": world,
        "snapshot_every": snapshot_every,
        "death_at_step": death_at,
        "recovered": lr is not None,
        "restore_source": lr["source"] if lr else None,
        "restored_step": lr["step"] if lr else None,
        # membership-change -> training-resumable (the supervisor's
        # elastic.recovery_ms for THIS recovery)
        "recovery_ms": round(lr["ms"], 1) if lr else None,
        # async capture->replicated wall per snapshot generation
        "snapshot_ms_mean": round((snap_h.sum - snap0[1]) / snaps, 2)
        if snaps else 0.0,
        "snapshots": int(snaps),
        "drill_wall_s": round(wall_s, 1),
        "completed": all(results.get(r) is True
                         for r in range(world - 1)),
    }


def bench_row(peak_flops=None, smoke=False):
    """The driver-facing row. ``smoke`` (CPU): tiny config, dp2 x pp2
    (x mp2 when partial-auto shard_map exists), accounting-only."""
    from paddle_tpu.models.gpt import GPTConfig

    if smoke:
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_seq_len=64, dropout=0.0)
        row = _measure_gpt_3d(cfg, dp=2, pp=2, mp=2, batch_per_dp=2,
                              seq=16, num_microbatches=2, steps=2,
                              warmup=1, overlap_steps=2)
        row["recovery"] = measure_recovery()
        return row
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=1024, dropout=0.0,
                    recompute=False)
    import jax
    ndev = len(jax.devices())
    # 4-chip target: dp2 x pp2 with TP folded in on >= 8 chips
    dp = 2 if ndev >= 4 else 1
    mp = 2 if ndev >= 8 else 1
    pp = 2 if ndev >= 4 else max(1, ndev)
    row = _measure_gpt_3d(cfg, dp=dp, pp=pp, mp=mp, batch_per_dp=8,
                          seq=1024, num_microbatches=8, steps=10,
                          warmup=2, peak_flops=peak_flops)
    # elastic recovery column (ISSUE 15): host-side drill — the
    # detector/snapshot/restore fabric under measurement is identical
    # on TPU pods; only the train step itself is device-bound
    row["recovery"] = measure_recovery()
    return row


FILES = ["benchmarks/hybrid_bench.py",
         "paddle_tpu/distributed/fleet/pipeline.py",
         "paddle_tpu/distributed/fleet/topology.py",
         "paddle_tpu/distributed/overlap.py",
         "paddle_tpu/distributed/parallel.py",
         "paddle_tpu/distributed/collective.py",
         "paddle_tpu/core/meshutil.py",
         "paddle_tpu/ops/pallas/flash_attention.py",
         # glue-fusion kernels + recompute policies sit inside the 3D
         # step's blocks (ISSUE 19): their code re-measures the row
         "paddle_tpu/ops/pallas/fused_residual_norm.py",
         "paddle_tpu/distributed/fleet/recompute.py",
         "paddle_tpu/models/gpt.py",
         # the gpt_3d skew/compile_ms columns come from the aggregator
         # (ISSUE 12): its merge/quantile math re-measures the row
         "paddle_tpu/observability/aggregate.py",
         "paddle_tpu/observability/tracing.py",
         # the recovery column (ISSUE 15) re-measures when the elastic
         # supervisor or the membership detector changes
         "paddle_tpu/resilience/elastic_train.py",
         "paddle_tpu/distributed/elastic.py"]


def main():
    import jax

    dev = jax.devices()[0]
    if len(jax.devices()) < 4:
        print("hybrid_bench: needs >= 4 devices; skipping",
              file=sys.stderr)
        return 0
    sys.path.insert(0, __file__.rsplit("/", 1)[0])
    import measured_cache as mc
    kind = str(getattr(dev, "device_kind", dev.platform))
    ver = mc.code_version(*FILES)
    row = mc.load(kind, "gpt_3d", ver)
    if row is None:
        row = bench_row(smoke=(dev.platform != "tpu"))
        mc.store(kind, "gpt_3d", ver, row)
    print(json.dumps({"gpt_3d": row}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
