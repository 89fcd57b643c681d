"""Live request migration & graceful drain (ISSUE 20).

Acceptance model: a request migrated between serving replicas
MID-FLIGHT — queued, mid-prefill, or mid-decode; fp or kv-quantized
pools; shared-prefix/COW pages; TP-sharded source and destination —
must produce EXACTLY the token stream of the unmigrated run (greedy
decode is deterministic and batch-invariant; the snapshot carries the
token prefix, so the restored KV bytes are the same pure function of
it).  On top of the bitwise bar: ``FleetRouter.drain`` must complete
without waiting out resident decodes (warm handoff, not a cold wait),
a planned preemption (SIGTERM through ``resilience.preempt``) must
lame-duck a replica and lose zero prefill work, a transfer that fails
past the retry budget must fall back to the PR17 cold requeue under
exactly one coded PDT-E025 flight record with demand counted once, a
torn (CRC-invalid) snapshot must be rejected at restore with the
source still serving, and a raced ``cancel`` must surface exactly one
``cancelled`` completion.  Pool conservation holds on every engine on
both sides of every move.

Shares the session ``serving_gpt`` and the serving-suite geometry, so
the compiled programs come off the session model's cache.
"""
import json
import os
import signal

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import errors
from paddle_tpu.inference import ContinuousBatchingEngine, FleetRouter
from paddle_tpu.resilience import faults, preempt

from test_serving_engine import _assert_pool_conserved

# ONE geometry for the whole module — matches test_serving_engine's /
# test_router's, so every engine reuses the session model's compiled
# serving programs
KW = dict(max_slots=2, page_size=8, max_seq_len=32, decode_window=4,
          prefill_chunk=8, q_block=2)


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    return serving_gpt


@pytest.fixture(scope="module")
def mesh2():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:2]), ("tp",))


def _workload(seed=0, sizes=(12, 9, 14), new=(8, 8, 8)):
    rng = np.random.default_rng(seed)
    return ([rng.integers(1, 96, (n,)).astype(np.int32)
             for n in sizes], list(new))


def _ref_stream(gpt, prompt, new, **kw):
    eng = ContinuousBatchingEngine(gpt, **{**KW, **kw})
    rid = eng.add_request(prompt, new)
    done = eng.run()
    _assert_pool_conserved(eng)
    return done[rid].sequence


def _migrate_mid_decode(src, dst, rid, min_done=2, max_steps=200):
    """Step ``src`` until ``rid`` is mid-decode with ``min_done``
    tokens emitted, then snapshot -> restore -> discard.  Returns the
    shipped payload."""
    payload = None
    for _ in range(max_steps):
        src.step()
        try:
            p = src.snapshot_request(rid)
        except (KeyError, ValueError):
            continue
        if p["phase"] == "decode" and len(p["done_toks"]) >= min_done:
            payload = p
            break
    assert payload is not None, "request never reached mid-decode"
    got = dst.restore_request(payload)
    assert got == rid
    assert src.discard_request(rid) is True
    return payload


# =============================================== engine-level moves ==

def test_migrate_mid_decode_bitwise(gpt):
    """The core claim: a stream migrated mid-decode equals the
    unmigrated stream token-for-token, both pools conserved, and the
    migration counters tell the story on each side."""
    prompts, new = _workload()
    ref = _ref_stream(gpt, prompts[0], new[0])
    src = ContinuousBatchingEngine(gpt, **KW)
    dst = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[0], new[0])
    payload = _migrate_mid_decode(src, dst, rid)
    assert payload["n_pages"] >= 1 and payload["pools"]
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, ref)
    assert done[rid].finish_reason == "length"
    src.run()
    _assert_pool_conserved(src)
    _assert_pool_conserved(dst)
    assert src.stats["migrated_out"] == 1
    assert src.stats["migrated_in"] == 0
    assert dst.stats["migrated_in"] == 1


def test_migrate_queued_and_mid_prefill(gpt):
    """A QUEUED request snapshots without pools and restores through
    the ordinary admission path; a MID-PREFILL request ships its
    finished chunks warm — the destination computes only the remaining
    prefill tokens (zero prefill work lost), stream bitwise."""
    prompts, new = _workload(seed=4, sizes=(20, 6), new=(6, 4))
    ref = _ref_stream(gpt, prompts[0], new[0])
    # queued: snapshot before any step admits it
    src = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[0], new[0])
    pay = src.snapshot_request(rid)
    assert pay["phase"] == "queued" and not pay["pools"]
    dst = ContinuousBatchingEngine(gpt, **KW)
    assert dst.restore_request(pay) == rid
    assert src.discard_request(rid) is True
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, ref)
    assert not src.has_work
    # mid-prefill: 20-token prompt, 8-token chunks -> step once so one
    # or two chunks are resident, then move the request warm
    src2 = ContinuousBatchingEngine(gpt, **KW)
    rid2 = src2.add_request(prompts[0], new[0])
    pay2 = None
    for _ in range(50):
        src2.step()
        try:
            p = src2.snapshot_request(rid2)
        except (KeyError, ValueError):
            continue
        if p["phase"] == "prefill" and p["prefill_off"] > 0:
            pay2 = p
            break
    assert pay2 is not None, "never caught the request mid-prefill"
    dst2 = ContinuousBatchingEngine(gpt, **KW)
    assert dst2.restore_request(pay2) == rid2
    assert src2.discard_request(rid2) is True
    done2 = dst2.run()
    np.testing.assert_array_equal(done2[rid2].sequence, ref)
    # the destination re-prefilled ONLY the unfinished suffix
    assert (dst2.stats["prefill_tokens_computed"]
            <= prompts[0].size - pay2["prefill_off"] + KW["page_size"])
    _assert_pool_conserved(src2)
    _assert_pool_conserved(dst2)


def test_migrate_kv_quant_bitwise(gpt):
    """Quantized KV pools (value + scale side-pools) ship and restore
    bitwise; a layout mismatch (fp destination) refuses coded."""
    prompts, new = _workload(seed=5)
    ref = _ref_stream(gpt, prompts[0], new[0], kv_quant=True)
    src = ContinuousBatchingEngine(gpt, kv_quant=True, **KW)
    dst = ContinuousBatchingEngine(gpt, kv_quant=True, **KW)
    rid = src.add_request(prompts[0], new[0])
    _migrate_mid_decode(src, dst, rid)
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, ref)
    src.run()
    _assert_pool_conserved(src)
    _assert_pool_conserved(dst)


def test_migrate_shared_prefix_cow_warm_destination(gpt):
    """Shared-prefix traffic: the destination already serves the same
    8-token prefix, so the restored request's prefix pages come off
    the destination's radix cache (COW at the divergence page) — the
    migrated stream is still bitwise and both pools conserve."""
    rng = np.random.default_rng(11)
    prefix = rng.integers(1, 96, 8).astype(np.int32)
    member = np.concatenate([prefix,
                             rng.integers(1, 96, 6).astype(np.int32)])
    leader = np.concatenate([prefix,
                             rng.integers(1, 96, 4).astype(np.int32)])
    ref = _ref_stream(gpt, member, 6)
    src = ContinuousBatchingEngine(gpt, **KW)
    dst = ContinuousBatchingEngine(gpt, **KW)
    dst.add_request(leader, 4)
    dst.run()                      # warm the destination's prefix cache
    rid = src.add_request(member, 6)
    _migrate_mid_decode(src, dst, rid)
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, ref)
    src.run()
    _assert_pool_conserved(src)
    _assert_pool_conserved(dst)


@pytest.mark.skipif("XLA_FLAGS" not in os.environ
                    or "host_platform_device_count" not in
                    os.environ.get("XLA_FLAGS", ""),
                    reason="needs the 8-device CPU mesh")
def test_migrate_tp2_to_tp2_bitwise(gpt, mesh2):
    """TP=2 source -> TP=2 destination: sharded pools gather into the
    snapshot, the restore re-shards through the import scatter's
    out_shardings, and the stream is bitwise the unsharded one."""
    prompts, new = _workload(seed=6)
    ref = _ref_stream(gpt, prompts[0], new[0])
    src = ContinuousBatchingEngine(gpt, mesh=mesh2, **KW)
    dst = ContinuousBatchingEngine(gpt, mesh=mesh2, **KW)
    rid = src.add_request(prompts[0], new[0])
    _migrate_mid_decode(src, dst, rid)
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, ref)
    src.run()
    _assert_pool_conserved(src)
    _assert_pool_conserved(dst)


def test_torn_snapshot_rejected_source_keeps(gpt):
    """The engine_snapshot_torn drill: a CRC-invalid payload is
    REJECTED at restore (MigrationError PDT-E025) — nothing lands on
    the destination, and the source (which never discarded) finishes
    the request normally."""
    prompts, new = _workload(seed=7)
    ref = _ref_stream(gpt, prompts[0], new[0])
    src = ContinuousBatchingEngine(gpt, **KW)
    dst = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[0], new[0])
    payload = None
    for _ in range(200):
        src.step()
        try:
            p = src.snapshot_request(rid)
        except (KeyError, ValueError):
            continue
        if p["phase"] == "decode" and len(p["done_toks"]) >= 2:
            payload = p
            break
    assert payload is not None
    faults.clear()
    faults.inject("engine_snapshot_torn", str(rid), times=1)
    try:
        with pytest.raises(errors.MigrationError) as ei:
            dst.restore_request(payload)
    finally:
        faults.clear()
    assert "PDT-E025" in str(ei.value)
    assert dst.stats["migrated_in"] == 0
    assert not dst.has_work
    _assert_pool_conserved(dst)
    done = src.run()               # source never stopped serving it
    np.testing.assert_array_equal(done[rid].sequence, ref)
    _assert_pool_conserved(src)


def test_cancel_race_exactly_one_cancelled(gpt):
    """Regression (ISSUE 20 bugfix): ``cancel(rid)`` racing an
    in-flight migration honors ``finish_reason="cancelled"`` on
    exactly one side — the source defers to its sweep (``discard``
    returns False) and the destination drops the restore."""
    prompts, new = _workload(seed=8)
    src = ContinuousBatchingEngine(gpt, **KW)
    dst = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[0], new[0])
    payload = None
    for _ in range(200):
        src.step()
        try:
            p = src.snapshot_request(rid)
        except (KeyError, ValueError):
            continue
        if p["phase"] == "decode" and len(p["done_toks"]) >= 2:
            payload = p
            break
    assert payload is not None
    got = dst.restore_request(payload)      # transfer already landed
    assert got == rid
    assert src.cancel(rid) is True          # ...when the cancel races
    # the source now refuses the discard: its sweep owns the finish
    assert src.discard_request(rid) is False
    assert dst.discard_request(rid) is True  # destination drops it
    done_src = src.run()
    done_dst = dst.run()
    cancelled = [c for c in list(done_src.values())
                 + list(done_dst.values())
                 if c.finish_reason == "cancelled"]
    assert len(cancelled) == 1 and cancelled[0].request_id == rid
    assert not done_dst                      # nothing finished there
    _assert_pool_conserved(src)
    _assert_pool_conserved(dst)
    # a snapshot taken AFTER the cancel refuses coded: migration must
    # skip a cancelling request, the sweep finalizes it
    src2 = ContinuousBatchingEngine(gpt, **KW)
    rid2 = src2.add_request(prompts[1], new[1])
    for _ in range(3):
        src2.step()
    assert src2.cancel(rid2) is True
    with pytest.raises(ValueError):
        src2.snapshot_request(rid2)
    src2.run()
    _assert_pool_conserved(src2)


# ================================================ router-level flow ==

def _fleet_pool_conserved(router):
    for rep in router._replicas:
        if rep.state != "dead" and hasattr(rep.engine, "_free_pages"):
            _assert_pool_conserved(rep.engine)


def _drive_fleet(gpt, prompts, new, drain_at=None, drain_name="r0",
                 **rkw):
    r = FleetRouter(gpt, replicas=2, replica_kwargs=KW,
                    heartbeat_timeout_ms=0, **rkw)
    rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
    done, steps = {}, 0
    while r.has_work:
        if drain_at is not None and steps == drain_at:
            assert r.drain(drain_name) is True
        for c in r.step():
            done[c.request_id] = c
        steps += 1
        assert steps < 2000, "fleet wedged"
    return r, rids, done


def test_router_drain_migrates_without_waiting(gpt):
    """Drain under load: the drained replica's residents move warm to
    the survivor mid-decode (migrations counted, pages shipped), every
    stream is bitwise the undrained run, the drained replica parks in
    standby, and no engine leaks a page."""
    prompts, new = _workload()
    r0, rids0, base = _drive_fleet(gpt, prompts, new, migration=False)
    r, rids, done = _drive_fleet(gpt, prompts, new, drain_at=3,
                                 migration=True)
    assert sorted(done) == sorted(rids)
    for a, b in zip(rids, rids0):
        np.testing.assert_array_equal(done[a].sequence,
                                      base[b].sequence)
    st = r.stats
    assert st["migrations"] >= 1 and st["migrated_pages"] >= 1
    assert st["migration_failures"] == 0 and st["deaths"] == 0
    assert r.replica_states()["r0"] == "standby"
    _fleet_pool_conserved(r)
    # the migrated requests FINISHED on the survivor, not the source
    assert r.replica("r0").stats["migrated_out"] >= 1
    assert r.replica("r1").stats["migrated_in"] >= 1


def test_router_migration_transient_absorbed(gpt):
    """The router_migration_transient drill inside the retry budget:
    the bounded envelope absorbs it (retry counter moves, zero
    failures) and the drained run stays bitwise."""
    prompts, new = _workload()
    _, rids0, base = _drive_fleet(gpt, prompts, new, migration=False)
    faults.clear()
    faults.inject("router_migration_transient", times=2)
    try:
        r, rids, done = _drive_fleet(gpt, prompts, new, drain_at=3,
                                     migration=True,
                                     migration_retries=3)
    finally:
        faults.clear()
    for a, b in zip(rids, rids0):
        np.testing.assert_array_equal(done[a].sequence,
                                      base[b].sequence)
    assert r.stats["migration_retries"] >= 2
    assert r.stats["migration_failures"] == 0
    assert r.stats["migrations"] >= 1
    _fleet_pool_conserved(r)


def test_router_migration_past_budget_cold_requeue(gpt, tmp_path,
                                                   monkeypatch):
    """Past the budget: the transfer gives up, ONE coded PDT-E025
    flight record per failed move is written, the request falls back
    to the PR17 cold requeue (front of its tenant queue) and completes
    bitwise — demand counted once (the fleet-wide requested total
    matches the clean run), zero leaked pages on either engine."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    prompts, new = _workload()
    rc, rids0, base = _drive_fleet(gpt, prompts, new, migration=False)
    req_clean = sum(rep.engine.stats["prefill_tokens_requested"]
                    for rep in rc._replicas)
    faults.clear()
    faults.inject("router_migration_transient", times=100)
    try:
        r, rids, done = _drive_fleet(gpt, prompts, new, drain_at=3,
                                     migration=True,
                                     migration_retries=1)
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)
    for a, b in zip(rids, rids0):
        np.testing.assert_array_equal(done[a].sequence,
                                      base[b].sequence)
    st = r.stats
    assert st["migrations"] == 0 and st["migration_failures"] >= 1
    assert st["requeues"] >= 1 and st["deaths"] == 0
    # demand counted once through the cold fallback (requeue=True)
    req_fault = sum(rep.engine.stats["prefill_tokens_requested"]
                    for rep in r._replicas)
    assert req_fault == req_clean
    _fleet_pool_conserved(r)
    recs = [f for f in sorted(os.listdir(tmp_path))
            if f.endswith(".json") and not f.endswith(".trace.json")]
    fails = []
    for f in recs:
        rec = json.load(open(os.path.join(tmp_path, f)))
        if rec.get("reason") == "router_migration_failed":
            fails.append(rec)
    assert len(fails) == st["migration_failures"]  # exactly one each
    for rec in fails:
        assert rec["error_code"] == "PDT-E025"
        assert rec["extra"]["fallback"] == "cold_requeue"


def test_router_torn_snapshot_falls_back(gpt):
    """Torn payload at the fleet level: the restore rejects, the
    source keeps serving (no requeue, no loss), the run is bitwise."""
    prompts, new = _workload()
    _, rids0, base = _drive_fleet(gpt, prompts, new, migration=False)
    faults.clear()
    faults.inject("engine_snapshot_torn", times=1)
    try:
        r, rids, done = _drive_fleet(gpt, prompts, new, drain_at=3,
                                     migration=True)
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)
    for a, b in zip(rids, rids0):
        np.testing.assert_array_equal(done[a].sequence,
                                      base[b].sequence)
    assert r.stats["migration_failures"] >= 1
    _fleet_pool_conserved(r)


def test_lameduck_sigterm_drill(gpt):
    """Planned preemption: SIGTERM through ``resilience.preempt`` puts
    the last live replica (never the last standing) into lame-duck —
    placements stop, residents migrate warm, the duck parks in standby
    — and every stream is bitwise the unpreempted run."""
    prompts, new = _workload()
    _, rids0, base = _drive_fleet(gpt, prompts, new, migration=False)
    assert preempt.install() is True
    try:
        r = FleetRouter(gpt, replicas=2, replica_kwargs=KW,
                        heartbeat_timeout_ms=0, migration=True)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        done, steps = {}, 0
        while r.has_work:
            if steps == 3:
                signal.raise_signal(signal.SIGTERM)
            for c in r.step():
                done[c.request_id] = c
            steps += 1
            assert steps < 2000, "preempt drill wedged"
    finally:
        preempt.uninstall()
        preempt.clear()
    assert sorted(done) == sorted(rids)
    for a, b in zip(rids, rids0):
        np.testing.assert_array_equal(done[a].sequence,
                                      base[b].sequence)
    assert r.stats["lameducks"] == 1
    assert r.replica_states()["r1"] == "standby"
    assert r.replica_states()["r0"] == "live"  # never the last one
    _fleet_pool_conserved(r)


def test_drain_under_storm_demand_counted_once(gpt):
    """Drain while a storm is still arriving: new placements avoid the
    draining replica, migrated + fresh requests all complete bitwise
    vs the drain-free storm, and warm moves re-prefill nothing (the
    fleet-wide requested total matches the clean run)."""
    prompts, new = _workload(seed=9, sizes=(12, 9, 14, 6, 10),
                             new=(6, 6, 6, 4, 4))

    def drive(drain):
        # 3 replicas: the survivors must have slot headroom while the
        # storm keeps arriving, or the warm move has nowhere to land
        r = FleetRouter(gpt, replicas=3, replica_kwargs=KW,
                        heartbeat_timeout_ms=0, migration=True)
        rids = [r.add_request(p, n)
                for p, n in zip(prompts[:3], new[:3])]
        pending = list(zip(prompts[3:], new[3:]))
        done, steps = {}, 0
        while r.has_work or pending:
            if drain and steps == 3:
                assert r.drain("r0") is True
            if pending and steps >= 2:
                p, n = pending.pop(0)
                rids.append(r.add_request(p, n))
            for c in r.step():
                done[c.request_id] = c
            steps += 1
            assert steps < 2000
        req = sum(rep.engine.stats["prefill_tokens_requested"]
                  for rep in r._replicas)
        return r, rids, done, req

    rc, rids_c, done_c, req_c = drive(False)
    rd, rids_d, done_d, req_d = drive(True)
    assert sorted(done_c) == sorted(rids_c)
    assert sorted(done_d) == sorted(rids_d)
    for a, b in zip(rids_c, rids_d):
        np.testing.assert_array_equal(done_c[a].sequence,
                                      done_d[b].sequence)
    assert rd.stats["migrations"] >= 1
    assert req_d == req_c                    # warm moves re-prefill 0
    _fleet_pool_conserved(rd)
