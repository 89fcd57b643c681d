"""Elastic training recovery drills (ISSUE 15): FleetSupervisor buddy
in-memory snapshots, collective watchdog (PDT-E021), detector-driven
resume, plus the satellite regressions (elastic store-key GC, coded
StoreTimeoutError PDT-E022).

Rig: multi-threaded TCPStore agents exactly like tests/test_elastic.py
and tests/test_rpc_store.py — each "rank" is a thread with its own
model, optimizer, data shard and store connections; the DP sync is the
supervisor's store-backed parameter allreduce (the CPU stand-in for
the in-graph psum).  Everything here is deterministic modulo wall
time: loss-parity assertions are EXACT equality.
"""
import os
import json
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import state as core_state
from paddle_tpu.core.errors import StoreTimeoutError
from paddle_tpu.distributed.store import TCPStore
from paddle_tpu.observability import metrics as om
from paddle_tpu.resilience import FleetSupervisor, faults
from paddle_tpu.resilience.elastic_train import _shard_view

pytestmark = pytest.mark.resilience

# drill timing: heartbeats fast enough that death detection (hb_timeout)
# and the collective deadline both land in a second or two, with
# margins wide enough for GIL load from W concurrent rank threads: a
# death is ten missed intervals, and both deadlines are six times the
# 0.25 s a ``slow_rank`` stalls
HB_INT, HB_TMO, COLL_MS = 0.15, 1.5, 1500.0


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


class _Data(paddle.io.Dataset):
    """Fixed regression set; global batch order is the contract every
    parity assertion leans on."""

    def __init__(self, n=128):
        rng = np.random.default_rng(0)
        self.x = rng.normal(size=(n, 4)).astype("float32")
        self.y = (self.x @ np.arange(1, 5, dtype="float32"))[:, None]

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


DATA = _Data()
BS = 2


def _make_model():
    paddle.seed(7)
    net = paddle.nn.Linear(4, 1)
    m = paddle.Model(net)
    opt = paddle.optimizer.SGD(parameters=net.parameters(),
                               learning_rate=0.05)
    m.prepare(opt, paddle.nn.MSELoss())
    return m


class _LossCb(paddle.hapi.callbacks.Callback):
    def __init__(self):
        self.losses = []

    def on_train_batch_end(self, step, logs=None):
        self.losses.append(float(logs["loss"]))


class _NoDisk:
    """CheckpointManager stand-in that PROVES zero disk reads on the
    buddy path: any consult is a test failure."""

    def latest_complete(self):
        raise AssertionError("disk consulted on the buddy path")

    def load(self, step=None):
        raise AssertionError("disk read on the buddy path")


def _run_fleet(port, W, num_iters, fault=(), snapshot_every=3,
               mgrs=None, timeout_ms=COLL_MS, join_s=60, close=True):
    """One fleet run: W rank threads against an externally hosted
    store.  Returns (models, sups, cbs, results).  Pass ``close=False``
    when the test still needs the supervisors' receiver threads (e.g.
    to wait for an async replica) — and close them itself."""
    models = [_make_model() for _ in range(W)]
    sups, cbs, results = [], [], {}
    faults.clear()
    for f in fault:
        faults.inject(*f)
    for r in range(W):
        sups.append(FleetSupervisor(
            "127.0.0.1", port, f"rank{r}", W, is_master=(r == 0),
            snapshot_every=snapshot_every,
            collective_timeout_ms=timeout_ms,
            heartbeat_interval=HB_INT, heartbeat_timeout=HB_TMO,
            recovery_timeout_s=45.0,
            checkpoint_manager=(mgrs[r] if mgrs else None)))
        cbs.append(_LossCb())

    def worker(r):
        try:
            results[r] = sups[r].fit(models[r], DATA, batch_size=BS,
                                     num_iters=num_iters,
                                     callbacks=[cbs[r]])
        except BaseException as e:  # surfaced by the caller's asserts
            results[r] = e

    ts = [threading.Thread(target=worker, args=(r,), daemon=True)
          for r in range(W)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(join_s)
        assert not t.is_alive(), \
            f"rank thread hung >{join_s}s: results={results}"
    if close:
        # close=False callers still have async replication in flight:
        # they clear faults + close in their own finally, AFTER
        # waiting for the replicas they assert on
        faults.clear()
        for s in sups:
            s.close()
    for r, res in results.items():
        assert not isinstance(res, BaseException), \
            f"rank {r} raised {type(res).__name__}: {res}"
    return models, sups, cbs, results


def _host():
    port = _free_port()
    return TCPStore("127.0.0.1", port, is_master=True), port


def _counter(name):
    return om.registry().counter(name).value


def _state_np(model):
    return {k: np.asarray(v.numpy())
            for k, v in model.network.state_dict().items()}


def _restart_reference(state, offset_batches, resume_step, num_iters):
    """The unfaulted restart: a fresh model carrying ``state`` fits the
    WORLD=1 remainder of the stream from ``offset_batches``, resuming
    the global step counter at ``resume_step`` — exactly what the
    recovered survivor does, minus every fault."""
    from paddle_tpu.core.tensor import Tensor
    m = _make_model()
    m.network.set_state_dict(
        {k: Tensor(np.asarray(v)) for k, v in state.items()})
    shard = _shard_view(DATA, BS, 0, 1, offset_batches)
    cb = _LossCb()
    m.fit(shard, batch_size=BS, epochs=1, shuffle=False, verbose=0,
          num_iters=num_iters, callbacks=[cb],
          resume=(0, 0, resume_step))
    return m, cb.losses


# --------------------------------------------------------------------------
# acceptance drill: rank death -> buddy restore -> loss parity
# --------------------------------------------------------------------------

def test_rank_dead_buddy_restore_loss_parity():
    """THE acceptance drill: rank1 dies at step 6 of a 2-rank fit
    (snapshots every 3).  The survivor gets a coded collective timeout,
    reshards to world 1, restores the buddy snapshot from step 3 with
    ZERO disk reads, fast-forwards the data position, and the
    post-recovery loss trajectory EQUALS an unfaulted restart at step 3
    on the same data order."""
    rec0 = _counter("elastic.recoveries")
    host, port = _host()
    try:
        models, sups, cbs, results = _run_fleet(
            port, 2, num_iters=12,
            fault=[("rank_dead", "1", 1, 6)],
            mgrs=[_NoDisk(), _NoDisk()])
    finally:
        host.close()
    assert results == {0: True, 1: False}
    assert sups[1].dead
    lr = sups[0].last_recovery
    assert lr is not None
    assert lr["source"] == "buddy"
    assert lr["step"] == 3          # newest snapshot before the death
    assert lr["consumed"] == 6      # 3 steps x world 2
    assert lr["dead"] == ["rank1"]
    assert lr["cause"] == "CollectiveTimeoutError"
    assert sups[0].world == 1 and sups[0].rank == 0
    assert _counter("elastic.recoveries") == rec0 + 1
    # 6 pre-fault losses + 9 post-recovery (global step resumes at 3,
    # num_iters=12)
    assert len(cbs[0].losses) == 15

    # unfaulted restart reference: 2-rank clean fleet to step 3 gives
    # the snapshot-consistent state (post-sync states are identical on
    # every rank), then a world-1 restart over the remaining stream
    host, port = _host()
    try:
        ref_models, _s, _c, ref_res = _run_fleet(port, 2, num_iters=3,
                                                 fault=())
    finally:
        host.close()
    assert ref_res == {0: True, 1: True}
    _m, ref_losses = _restart_reference(_state_np(ref_models[0]),
                                        offset_batches=6,
                                        resume_step=3, num_iters=12)
    assert cbs[0].losses[6:] == ref_losses
    # and the final parameters match bitwise, not just the losses
    end = _state_np(models[0])
    ref_end = _state_np(_m)
    assert set(end) == set(ref_end)
    for k in end:
        assert np.array_equal(end[k], ref_end[k]), k


def test_multi_survivor_resharding_stays_lockstep():
    """3 ranks, ONE death: the two survivors roll back together,
    reshard to world 2, and keep training IN LOCKSTEP — their
    parameters are bitwise-identical at every synced step, so at the
    end.  Regression for the rolled-back-step collective keys: re-run
    steps must not consume a peer's stale pre-crash contribution (the
    allreduce epoch namespace), or survivors silently diverge."""
    host, port = _host()
    try:
        models, sups, cbs, results = _run_fleet(
            port, 3, num_iters=9, snapshot_every=2,
            fault=[("rank_dead", "2", 1, 5)],
            mgrs=[_NoDisk()] * 3)
    finally:
        host.close()
    assert results == {0: True, 1: True, 2: False}
    for r in (0, 1):
        lr = sups[r].last_recovery
        assert lr is not None and lr["source"] == "buddy"
        assert lr["step"] == 4 and lr["dead"] == ["rank2"]
        assert sups[r].world == 2 and sups[r].rank == r
    s0, s1 = _state_np(models[0]), _state_np(models[1])
    for k in s0:
        assert np.array_equal(s0[k], s1[k]), \
            f"survivors diverged on {k}: {s0[k]} vs {s1[k]}"


def test_two_deaths_buddy_chain():
    """rank1 AND its buddy rank2 die together in a 3-rank fleet: the
    plan skips rank1 (its holder died with it) and restores from
    rank2's replica, held by the surviving rank0 — still no disk."""
    host, port = _host()
    try:
        models, sups, cbs, results = _run_fleet(
            port, 3, num_iters=10,
            fault=[("rank_dead", "1", 1, 5), ("rank_dead", "2", 1, 5)],
            mgrs=[_NoDisk()] * 3, snapshot_every=2)
    finally:
        host.close()
    assert results == {0: True, 1: False, 2: False}
    lr = sups[0].last_recovery
    assert lr is not None and lr["source"] == "buddy"
    assert set(lr["dead"]) == {"rank1", "rank2"}
    assert lr["step"] == 4
    assert sups[0].world == 1


def test_disk_fallback_when_no_buddy_replica(tmp_path):
    """Snapshots disabled (the no-surviving-replica limit case): the
    dead rank leaves nothing in peer memory, so recovery falls to the
    newest COMPLETE CheckpointManager version — and the post-recovery
    trajectory equals a from-scratch world-1 restart at that version's
    position."""
    from paddle_tpu.resilience.checkpoint import CheckpointManager

    seed_model = _make_model()
    mgr = CheckpointManager(tmp_path / "ckpt")
    # shape the checkpoint like Model._resilient_save does: rng as a
    # PLAIN ndarray (the restore path must not assume Tensor), and a
    # recorded epoch >= 1 (single-epoch stream semantics must restart
    # the remaining data at epoch 0, not skip fit's whole epoch range)
    core_state.default_rng.seed(0)
    rng_arr = np.asarray(core_state.default_rng._key_var._read())
    mgr.save({"model": seed_model.network.state_dict(),
              "rng": rng_arr}, 0,
             meta={"global_step": 0, "consumed": 0, "epoch": 1})
    host, port = _host()
    try:
        models, sups, cbs, results = _run_fleet(
            port, 2, num_iters=8, snapshot_every=0,
            fault=[("rank_dead", "1", 1, 4)], mgrs=[mgr, mgr])
    finally:
        host.close()
    assert results == {0: True, 1: False}
    lr = sups[0].last_recovery
    assert lr is not None and lr["source"] == "disk"
    assert lr["step"] == 0 and lr["consumed"] == 0
    # 4 pre-fault + 8 from-scratch world-1 steps
    assert len(cbs[0].losses) == 12
    _m, ref_losses = _restart_reference(_state_np(seed_model),
                                        offset_batches=0,
                                        resume_step=0, num_iters=8)
    assert cbs[0].losses[4:] == ref_losses


# --------------------------------------------------------------------------
# detector vs straggler separation
# --------------------------------------------------------------------------

def test_slow_rank_does_not_trigger_recovery():
    """A straggler stalls inside the collective deadline while its
    heartbeats keep flowing: peers absorb the wait, NO recovery runs,
    and the math is untouched (bitwise vs the uninjected run)."""
    rec0 = _counter("elastic.recoveries")
    host, port = _host()
    try:
        _m, sups, cbs, results = _run_fleet(
            port, 2, num_iters=5,
            fault=[("slow_rank", "1", 2, 2)])
    finally:
        host.close()
    assert results == {0: True, 1: True}
    assert all(s.last_recovery is None for s in sups)
    assert _counter("elastic.recoveries") == rec0
    host, port = _host()
    try:
        _m2, _s2, clean_cbs, _r2 = _run_fleet(port, 2, num_iters=5)
    finally:
        host.close()
    assert cbs[0].losses == clean_cbs[0].losses
    assert cbs[1].losses == clean_cbs[1].losses


# --------------------------------------------------------------------------
# collective watchdog: coded failure + exactly one flight dump
# --------------------------------------------------------------------------

def test_hung_collective_dumps_once_with_stacks(tmp_path, monkeypatch):
    """The dead peer's hang surfaces as PDT-E021 WITHIN the collective
    deadline (the drill completes in bounded wall time instead of
    hanging tier-1), with exactly ONE flight dump containing every
    thread's stack."""
    from paddle_tpu.observability import watchdog as wd

    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    host, port = _host()
    t0 = time.monotonic()
    try:
        _m, sups, cbs, results = _run_fleet(
            port, 2, num_iters=6, snapshot_every=2,
            fault=[("rank_dead", "1", 1, 4)], mgrs=[_NoDisk()] * 2)
    finally:
        host.close()
    wall = time.monotonic() - t0
    assert results == {0: True, 1: False}
    lr = sups[0].last_recovery
    assert lr["cause"] == "CollectiveTimeoutError"
    # bounded detection: heartbeat expiry + collective deadline + the
    # recovery itself, all inside a wall budget that an infinite hang
    # would blow immediately
    assert wall < 45.0
    dumps = [f for f in os.listdir(tmp_path)
             if f.startswith("flight_") and f.endswith(".json")
             and not f.endswith(".trace.json")]
    assert len(dumps) == 1, dumps
    with open(tmp_path / dumps[0]) as f:
        rec = json.load(f)
    stacks = rec["extra"]["stacks"]
    assert stacks, "flight record carries no thread stacks"
    assert any("_allreduce_mean" in "".join(str(fr) for fr in frames)
               for frames in stacks.values())
    assert wd.armed() == []  # every token disarmed after the run


# --------------------------------------------------------------------------
# metrics-off: bitwise no-op, recovery still functions
# --------------------------------------------------------------------------

def test_metrics_off_bitwise_noop(tmp_path, monkeypatch):
    """PDTPU_METRICS=off restores pre-observability behavior bitwise:
    the same faulted drill produces the SAME losses and the SAME
    recovery (the supervisor's hard deadline replaces the watchdog), no
    flight dumps, and no counter movement."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    host, port = _host()
    try:
        _m, sups_on, cbs_on, res_on = _run_fleet(
            port, 2, num_iters=8, snapshot_every=2,
            fault=[("rank_dead", "1", 1, 4)], mgrs=[_NoDisk()] * 2)
    finally:
        host.close()

    old = core_state.get_flag("metrics")
    core_state.set_flags({"metrics": False})
    try:
        snaps0 = _counter("elastic.snapshots")
        rec0 = _counter("elastic.recoveries")
        host, port = _host()
        try:
            _m2, sups_off, cbs_off, res_off = _run_fleet(
                port, 2, num_iters=8, snapshot_every=2,
                fault=[("rank_dead", "1", 1, 4)], mgrs=[_NoDisk()] * 2)
        finally:
            host.close()
        assert _counter("elastic.snapshots") == snaps0
        assert _counter("elastic.recoveries") == rec0
    finally:
        core_state.set_flags({"metrics": old})

    assert res_on == res_off == {0: True, 1: False}
    assert cbs_on[0].losses == cbs_off[0].losses
    on, off = sups_on[0].last_recovery, sups_off[0].last_recovery
    assert off is not None
    assert (on["source"], on["step"], on["consumed"]) \
        == (off["source"], off["step"], off["consumed"])
    assert off["cause"] == "CollectiveTimeoutError"
    # observability off is observability off: no stray flight records
    dumps_off = [f for f in os.listdir(tmp_path)
                 if f.endswith(".json")
                 and not f.endswith(".trace.json")]
    assert len(dumps_off) == 1  # only the metrics-ON run's dump


# --------------------------------------------------------------------------
# snapshot machinery: cadence, counters, torn replicas, partition retry
# --------------------------------------------------------------------------

def _wait_replicas(sup, src, want_steps, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        held = {s for s, _m, _p in sup._replicas.get(src, [])}
        if want_steps <= held:
            return held
        time.sleep(0.05)
    return {s for s, _m, _p in sup._replicas.get(src, [])}


def test_snapshot_cadence_and_counters():
    """Clean run accounting: captures at every cadence boundary on
    every rank, replication wall time observed, nothing torn, nothing
    recovered, generation gauge at the initial rendezvous."""
    reg = om.registry()
    snaps0 = _counter("elastic.snapshots")
    torn0 = _counter("elastic.snapshots_torn")
    rec0 = _counter("elastic.recoveries")
    ms0 = reg.histogram("elastic.snapshot_ms").count
    host, port = _host()
    sups = []
    try:
        _m, sups, _c, results = _run_fleet(port, 2, num_iters=6,
                                           snapshot_every=3,
                                           close=False)
        # replication is async off the step path: wait for the buddies
        # to actually hold each other's generations before closing
        held0 = _wait_replicas(sups[0], "rank1", {3, 6})
        held1 = _wait_replicas(sups[1], "rank0", {3, 6})
    finally:
        faults.clear()
        for s in sups:
            s.close()
        host.close()
    assert results == {0: True, 1: True}
    assert _counter("elastic.snapshots") == snaps0 + 4  # 2 ranks x 2
    assert _counter("elastic.snapshots_torn") == torn0
    assert _counter("elastic.recoveries") == rec0
    pushed = reg.histogram("elastic.snapshot_ms").count - ms0
    assert 1 <= pushed <= 4  # latest-wins queue may skip, never grow
    assert held0 == {3, 6} and held1 == {3, 6}
    assert reg.gauge("elastic.generation").value == 1


def test_snapshot_torn_falls_back_to_previous_generation():
    """The snapshot_torn drill: rank1's step-6 replica is half-written
    (manifest records full size/CRC); the buddy's validation rejects it
    and keeps step 3 — which is exactly what recovery restores when
    rank1 dies at step 8."""
    torn0 = _counter("elastic.snapshots_torn")
    host, port = _host()
    try:
        _m, sups, cbs, results = _run_fleet(
            port, 2, num_iters=12,
            fault=[("snapshot_torn", "1", 1, 2),
                   ("rank_dead", "1", 1, 8)],
            mgrs=[_NoDisk()] * 2)
    finally:
        host.close()
    assert results == {0: True, 1: False}
    assert _counter("elastic.snapshots_torn") >= torn0 + 1
    lr = sups[0].last_recovery
    assert lr["source"] == "buddy"
    assert lr["step"] == 3  # torn 6 rejected, previous generation kept


def test_store_partition_bounded_retry():
    """store_partition exhausts the push budget on rank0's FIRST
    snapshot replication (3 injected failures vs 3 attempts): that
    generation is skipped, the failure counted, and the NEXT cadence
    boundary replicates fine — training never notices."""
    fail0 = _counter("elastic.snapshot_push_failures")
    host, port = _host()
    sups = []
    try:
        _m, sups, _c, results = _run_fleet(
            port, 2, num_iters=6, snapshot_every=3,
            fault=[("store_partition", "rank0", 3, 1)], close=False)
        held = _wait_replicas(sups[1], "rank0", {6})
    finally:
        faults.clear()
        for s in sups:
            s.close()
        host.close()
    assert results == {0: True, 1: True}
    assert all(s.last_recovery is None for s in sups)
    assert _counter("elastic.snapshot_push_failures") == fail0 + 1
    assert 6 in held  # the step-6 push survived the healed partition


# --------------------------------------------------------------------------
# satellite: elastic store-key GC across churn
# --------------------------------------------------------------------------

def test_elastic_store_keys_stable_across_churn(monkeypatch):
    """Departed nodes' elastic/* keys are GC'd by the master: N
    join/leave cycles leave the store key count flat instead of growing
    one key set per churn event."""
    from paddle_tpu.distributed.elastic import ElasticManager

    monkeypatch.setenv("PDTPU_NATIVE_STORE", "0")  # countable _data
    port = _free_port()
    host = TCPStore("127.0.0.1", port, is_master=True)
    try:
        master = ElasticManager(
            TCPStore("127.0.0.1", port), "anchor", True,
            heartbeat_interval=0.15, heartbeat_timeout=0.6,
            min_nodes=1)
        gen, members = master.start()
        assert members == ["anchor"]

        def elastic_keys():
            with host._server._cv:
                return sorted(k.decode() for k in host._server._data
                              if k.startswith(b"elastic/"))

        def churn(i, gen):
            st = TCPStore("127.0.0.1", port)
            m = ElasticManager(st, f"joiner{i}", False,
                               heartbeat_interval=0.15,
                               heartbeat_timeout=0.6, min_nodes=1)
            res = {}
            t = threading.Thread(
                target=lambda: res.update(g=m.start()), daemon=True)
            t.start()
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                g, mem = master.wait_generation(gen, timeout=0.5)
                if g > gen and f"joiner{i}" in mem:
                    gen = g
                    break
            else:
                raise AssertionError(f"joiner{i} never admitted")
            t.join(10)
            m.stop()  # leaves: heartbeat expires, master evicts + GCs
            while time.monotonic() < deadline:
                g, mem = master.wait_generation(gen, timeout=0.5)
                if g > gen and mem == ["anchor"]:
                    gen = g
                    break
            else:
                raise AssertionError(f"joiner{i} never evicted")
            st.close()
            return gen

        counts = []
        for i in range(3):
            gen = churn(i, gen)
            time.sleep(0.5)  # one scan pass for the hb-key re-delete
            counts.append(len(elastic_keys()))
        # stable, not linear in churn: every cycle ends at the same
        # footprint once the departed joiner's keys are collected
        assert counts[0] == counts[1] == counts[2], \
            (counts, elastic_keys())
        keys = elastic_keys()
        assert not any(f"joiner{i}" in k for i in range(3)
                       for k in keys), keys
        # membership history bounded too
        assert sum(k.startswith("elastic/members/")
                   for k in keys) <= 4
        master.stop()
    finally:
        host.close()


def test_elastic_dropped_node_readmitted_after_slot_gc(monkeypatch):
    """Key GC must not strand a transiently-dropped node: once the
    master retires its registration slot, the healed agent re-registers
    itself (``_ensure_registered``) and is re-admitted — the pre-GC
    'dropped: wait to be re-seen' launcher contract still holds."""
    from paddle_tpu.distributed.elastic import ElasticManager

    port = _free_port()
    host = TCPStore("127.0.0.1", port, is_master=True)
    try:
        master = ElasticManager(
            TCPStore("127.0.0.1", port), "anchor", True,
            heartbeat_interval=0.15, heartbeat_timeout=0.6,
            min_nodes=1)
        gen, members = master.start()
        j = ElasticManager(
            TCPStore("127.0.0.1", port), "flapper", False,
            heartbeat_interval=0.15, heartbeat_timeout=0.6,
            min_nodes=1)
        jres = {}
        threading.Thread(target=lambda: jres.update(g=j.start()),
                         daemon=True).start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            g, mem = master.wait_generation(gen, timeout=0.5)
            if g > gen and "flapper" in mem:
                gen = g
                break
        else:
            raise AssertionError("flapper never admitted")

        # the launcher's dropped-node loop: keep watching generations
        # (this is also what refreshes j's cached membership, which
        # _ensure_registered keys off)
        seen = {"dropped": False, "back": False}

        def watch():
            wg = jres["g"][0] if "g" in jres else 0
            end = time.monotonic() + 30
            while time.monotonic() < end and not seen["back"]:
                try:
                    wg2, wm = j.wait_generation(wg, timeout=0.5)
                except Exception:
                    continue
                if wg2 > wg:
                    wg = wg2
                    if "flapper" not in wm:
                        seen["dropped"] = True
                    elif seen["dropped"]:
                        seen["back"] = True

        threading.Thread(target=watch, daemon=True).start()

        # simulate a partition: the flapper's beats stop flowing but
        # the agent stays alive
        real_beat = j._beat
        j._beat = lambda: None
        while time.monotonic() < deadline:
            g, mem = master.wait_generation(gen, timeout=0.5)
            if g > gen and mem == ["anchor"]:
                gen = g
                break
        else:
            raise AssertionError("flapper never evicted")
        time.sleep(0.6)  # a GC pass retires the slot + hb tombstone

        # partition heals: beats resume on the (now GC'd) identity
        j._beat = real_beat
        while time.monotonic() < deadline:
            g, mem = master.wait_generation(gen, timeout=0.5)
            if g > gen and "flapper" in mem:
                gen = g
                break
        else:
            raise AssertionError(
                "healed flapper never re-admitted after slot GC")
        # and the agent itself observed the round trip
        t_end = time.monotonic() + 10
        while time.monotonic() < t_end and not seen["back"]:
            time.sleep(0.1)
        assert seen["dropped"] and seen["back"], seen
        j.stop()
        master.stop()
    finally:
        host.close()


# --------------------------------------------------------------------------
# satellite: coded StoreTimeoutError (PDT-E022)
# --------------------------------------------------------------------------

def test_store_timeout_error_coded():
    """get/wait deadline expiry raises the coded StoreTimeoutError
    (PDT-E022), still a TimeoutError for old callers, and a timeout is
    a SERVED answer — never retried as a transport failure."""
    port = _free_port()
    host = TCPStore("127.0.0.1", port, is_master=True)
    try:
        client = TCPStore("127.0.0.1", port)
        with pytest.raises(StoreTimeoutError) as ei:
            client.get("never/appears", timeout=0.2)
        assert ei.value.error_code == "PDT-E022"
        assert "PDT-E022" in str(ei.value)
        assert isinstance(ei.value, TimeoutError)
        with pytest.raises(StoreTimeoutError):
            client.wait(["also/never"], timeout=0.2)
        # a timeout consumed no retry budget: the connection is fine
        client.set("k", b"v")
        assert client.get("k", timeout=1.0) == b"v"
        client.close()
    finally:
        host.close()


# --------------------------------------------------------------------------
# unit: batch-granular reshard reconstructs the exact remaining stream
# --------------------------------------------------------------------------

def test_shard_view_reshard_exact_stream():
    """Carrying the consumed-batch offset across a world-size change
    reconstructs exactly the remaining global batch stream — the
    property the loss-parity drills lean on."""
    n, bs = 48, 2
    data = [(np.float32(i), np.float32(i)) for i in range(n)]

    def batches(shard):
        return [tuple(float(shard[b * bs + r][0]) for r in range(bs))
                for b in range(len(shard) // bs)]

    # world 3 consumes 9 global batches (3 steps), then reshards to 2
    consumed = 9
    remaining = [tuple(float(data[g * bs + r][0]) for r in range(bs))
                 for g in range(consumed, n // bs)]
    got = [None] * len(remaining)
    for rank in range(2):
        sh = batches(_shard_view(data, bs, rank, 2, consumed))
        for b, item in enumerate(sh):
            got[b * 2 + rank] = item
    # trailing ragged batches (not divisible by the new world) stay
    # unconsumed by construction — strip the None tail
    while got and got[-1] is None:
        got.pop()
    assert got == remaining[:len(got)]
    assert len(remaining) - len(got) < 2  # at most world-1 dropped
