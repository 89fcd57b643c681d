"""Fleet-scale serving router (ISSUE 17; ``inference/router.py``).

Acceptance model: a :class:`FleetRouter` spreading a workload over N
replicas must produce EXACTLY the greedy token streams of one engine
serving the same requests — placement, tenant fair share, dead-replica
requeue and elastic scale-out are all scheduling, and scheduling may
never move a token (greedy decode is batch-invariant).  On top of the
bitwise bar: affinity must measurably beat round-robin on cache-hit
tokens, a starved tenant must keep its weighted share, a killed
replica's requests must all complete on survivors under exactly one
coded PDT-E024 flight record, and a sustained fleet-SLO burn must
admit the standby.

Shares the session ``serving_gpt`` and the serving-suite geometry, so
the compiled programs come off the session model's cache.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.core import errors
from paddle_tpu.inference import (ContinuousBatchingEngine, DisaggServer,
                                  FleetRouter, TenantSpec)
from paddle_tpu.observability import watchdog as wdog
from paddle_tpu.observability.slo import parse_slo
from paddle_tpu.resilience import faults

from test_serving_engine import _assert_pool_conserved

# ONE geometry for the whole module — matches test_serving_engine's /
# test_distserve's, so every replica engine reuses the session model's
# compiled serving programs
KW = dict(max_slots=2, page_size=8, max_seq_len=32, decode_window=4,
          prefill_chunk=8, q_block=2)


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    return serving_gpt


@pytest.fixture()
def metrics_on():
    """Force the metrics flag on for one test, restoring after."""
    old = paddle.get_flags("metrics")["metrics"]
    paddle.set_flags({"metrics": True})
    yield
    paddle.set_flags({"metrics": old})


def _workload(seed=0, sizes=(5, 9, 3, 12), new=(6, 4, 7, 5)):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, 96, (n,)).astype(np.int32)
             for n in sizes], list(new))


@pytest.fixture(scope="module")
def refs(gpt):
    """Single-engine streams for the shared workload — the bar every
    fleet variant must hit bitwise."""
    prompts, new = _workload()
    eng = ContinuousBatchingEngine(gpt, **KW)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    _assert_pool_conserved(eng)
    return prompts, new, [done[r].sequence for r in rids]


def _fleet_pool_conserved(router):
    for rep in router._replicas:
        if rep.state != "dead" and hasattr(rep.engine, "_free_pages"):
            _assert_pool_conserved(rep.engine)


# ========================================================== routing ==

def test_fleet_bitwise_vs_single_engine(gpt, refs):
    """The basic spread: N replicas serve the single-engine workload
    token-identically, every replica pool conserved."""
    prompts, new, seqs = refs
    r = FleetRouter(gpt, replicas=3, replica_kwargs=KW)
    rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
    done = r.run()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = r.stats
    assert st["placed"] == len(prompts) and st["deaths"] == 0
    assert st["replicas_live"] == 3 and st["queue_depth"] == 0
    _fleet_pool_conserved(r)


def test_eager_admission_errors(gpt):
    """Fleet-level PDT-E016/PDT-E017: an unservable request rejects at
    submission; a full router queue sheds under the reject policy."""
    r = FleetRouter(gpt, replicas=2, replica_kwargs=KW, max_queue=2)
    with pytest.raises(errors.PageBudgetError) as ei:
        r.add_request(np.arange(20, dtype=np.int32), 64)
    assert "PDT-E016" in str(ei.value)
    p = np.arange(4, dtype=np.int32)
    r.add_request(p, 2)
    r.add_request(p, 2)
    with pytest.raises(errors.QueueFullError) as ei:
        r.add_request(p, 2)
    assert "PDT-E017" in str(ei.value)
    assert r.stats["rejected"] == 2
    r.run()


def test_affinity_beats_round_robin(gpt):
    """Shared-prefix storm over 3 replicas, leaders warmed first:
    cache-aware placement routes each group member to the replica
    holding its prefix pages, so the fleet-wide cache-hit tokens beat
    round-robin's scatter — with identical token streams (placement
    is scheduling, not semantics)."""
    rng = np.random.default_rng(7)
    groups = []
    for _ in range(3):
        prefix = rng.integers(0, 96, 8).astype(np.int32)
        groups.append([np.concatenate([
            prefix, rng.integers(0, 96, 6).astype(np.int32)])
            for _ in range(3)])
    leaders = [g[0] for g in groups]
    # group-consecutive storm order: round-robin NECESSARILY scatters
    # each group's members across replicas, affinity concentrates them
    storm = [p for g in groups for p in g[1:]]

    def drive(affinity):
        r = FleetRouter(gpt, replicas=3, replica_kwargs=KW,
                        affinity=affinity)
        for p in leaders:
            r.add_request(p, 4)
        done = r.run()
        pending = list(storm)
        while r.has_work or pending:
            if pending:
                r.add_request(pending.pop(0), 4)
            for c in r.step():
                done[c.request_id] = c
        hits = sum(rep.engine.stats["cache_hit_tokens"]
                   for rep in r._replicas)
        _fleet_pool_conserved(r)
        return r, done, hits

    ra, da, hits_aff = drive(True)
    rr, dr, hits_rr = drive(False)
    assert sorted(da) == sorted(dr)
    for rid in da:
        np.testing.assert_array_equal(da[rid].sequence,
                                      dr[rid].sequence)
    # every storm member's 8-token prefix is cached SOMEWHERE after
    # the warm phase: affinity must collect them all, round-robin
    # lands one only when the rotation happens to line up
    assert hits_aff == 8 * len(storm)
    assert hits_aff > hits_rr
    assert ra.stats["affinity_hits"] >= len(storm)


def test_fair_share_starved_tenant_floor(gpt):
    """Skewed-tenant storm through ONE replica (2 slots): a flooding
    weight-1 tenant vs an equal-weight light tenant.  Stride
    scheduling must interleave the light tenant's requests into the
    early placements instead of parking them behind the flood — the
    starved tenant's completions land within its fair window, not
    after the storm drains."""
    rng = np.random.default_rng(3)
    storm = [rng.integers(0, 96, 6).astype(np.int32) for _ in range(8)]
    light = [rng.integers(0, 96, 6).astype(np.int32) for _ in range(2)]
    r = FleetRouter(
        gpt, replicas=1, replica_kwargs=KW,
        tenants=[TenantSpec("storm", weight=1.0),
                 TenantSpec("light", weight=1.0)])
    storm_rids = [r.add_request(p, 4, tenant="storm") for p in storm]
    light_rids = [r.add_request(p, 4, tenant="light") for p in light]
    order = []
    while r.has_work:
        order.extend(c.request_id for c in r.step())
    assert sorted(order) == sorted(storm_rids + light_rids)
    # equal weights, equal per-request cost: the light tenant's 2
    # requests finish in the first half of the drain even though the
    # storm tenant enqueued 8 requests first
    first_half = set(order[:len(order) // 2])
    assert set(light_rids) <= first_half
    # strict priority dominates weights: a priority-0 tenant admitted
    # into the same storm places before any remaining storm request
    r2 = FleetRouter(
        gpt, replicas=1, replica_kwargs=KW,
        tenants=[TenantSpec("storm", weight=10.0, priority=1),
                 TenantSpec("vip", weight=1.0, priority=0)])
    srids = [r2.add_request(p, 4, tenant="storm") for p in storm]
    vrid = r2.add_request(light[0], 4, tenant="vip")
    order2 = []
    while r2.has_work:
        order2.extend(c.request_id for c in r2.step())
    # the vip request overtakes every storm request still queued at
    # its arrival (the first 4 rode the 2*max_slots admission window)
    assert order2.index(vrid) < len(order2) - 2


# ================================================= replica failure ==

def test_replica_kill_mid_decode_bitwise(gpt, refs, tmp_path,
                                         monkeypatch, metrics_on):
    """THE acceptance drill: 3 replicas, one killed mid-decode.  Every
    affected request completes on a survivor bitwise-identical to the
    unfaulted run, no request is lost, nothing hangs, and exactly one
    coded flight record (PDT-E024) is written."""
    monkeypatch.setenv("PDTPU_FLIGHT_DIR", str(tmp_path))
    prompts, new, seqs = refs
    faults.clear()
    obs.events.clear()
    try:
        r = FleetRouter(gpt, replicas=3, replica_kwargs=KW)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        done, steps = {}, 0
        while r.has_work:
            if steps == 2:       # mid-decode: kill a loaded replica
                victim = max((rep for rep in r._replicas
                              if rep.state == "live"),
                             key=lambda rep: len(rep.rids))
                assert victim.rids, "drill needs in-flight work"
                faults.inject("router_replica_lost", victim.name)
            for c in r.step():
                done[c.request_id] = c
            steps += 1
            assert steps < 2000, "kill drill wedged"
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)          # no request lost
    for rid, ref in zip(rids, seqs):             # ...and none moved
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = r.stats
    assert st["deaths"] == 1 and st["replicas_dead"] == 1
    assert st["requeues"] >= 1 and st["generation"] == 1
    _fleet_pool_conserved(r)
    recs = [f for f in sorted(os.listdir(tmp_path))
            if f.endswith(".json") and not f.endswith(".trace.json")]
    assert len(recs) == 1                # exactly one flight record
    rec = json.load(open(os.path.join(tmp_path, recs[0])))
    assert rec["reason"] == "router_replica_lost"
    assert rec["error_code"] == "PDT-E024"
    assert rec["extra"]["replica"] == victim.name
    assert rec["extra"]["requeued"] == st["requeues"]


def test_all_replicas_dead_raises_coded(gpt):
    """Losing the LAST replica with work queued surfaces PDT-E024
    instead of a silent hang (no standby to fail over to)."""
    faults.clear()
    try:
        r = FleetRouter(gpt, replicas=1, replica_kwargs=KW)
        r.add_request(np.arange(5, dtype=np.int32), 4)
        faults.inject("router_replica_lost", "r0")
        with pytest.raises(errors.ReplicaLostError) as ei:
            for _ in range(10):
                r.step()
    finally:
        faults.clear()
    assert "PDT-E024" in str(ei.value)


def test_dispatch_transient_retries(gpt, refs):
    """A transient placement failure retries inside the dispatch
    envelope (counter moves) without killing the replica; the request
    still completes bitwise."""
    prompts, new, seqs = refs
    faults.clear()
    try:
        r = FleetRouter(gpt, replicas=2, replica_kwargs=KW,
                        dispatch_retries=3)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        faults.inject("router_dispatch_transient", str(rids[0]),
                      times=2)
        done = r.run()
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    assert r.stats["retries"] == 2 and r.stats["deaths"] == 0


def test_dispatch_exhausted_kills_and_requeues(gpt, refs):
    """A placement that fails past the retry budget declares the
    replica dead; the request (and the replica's whole load) requeues
    to the survivor and completes bitwise."""
    prompts, new, seqs = refs
    faults.clear()
    try:
        r = FleetRouter(gpt, replicas=2, replica_kwargs=KW,
                        dispatch_retries=1)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        # exactly the retry budget (dispatch_retries=1 -> 2 attempts):
        # the replica dies, and the survivor's re-placement is clean
        faults.inject("router_dispatch_transient", str(rids[0]),
                      times=2)
        done = r.run()
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    assert r.stats["deaths"] == 1


# =============================================== elastic scale-out ==

def _breach_specs():
    """A queue-wait objective tiny enough that real traffic breaches
    it immediately, with second-scale windows so the fake clock can
    walk the burn rates over threshold in a few steps."""
    specs = parse_slo("queue_p95_ms=0.001")
    for s in specs:
        s.fast_window_s = 1.0
        s.slow_window_s = 4.0
    return specs


def test_scaleout_on_burn_breach_and_scalein(gpt, metrics_on):
    """Sustained fleet-SLO burn admits the standby (warm model, cold
    cache); holding recovered for scalein_hold_s drains it back to
    standby once idle.  Deterministic clock — no sleeps."""
    t = [0.0]
    r = FleetRouter(gpt, replicas=1, replica_kwargs=KW, standby=1,
                    fleet_slo=_breach_specs(), clock=lambda: t[0],
                    scalein_hold_s=5.0)
    assert r.replica_states() == {"r0": "live", "r1": "standby"}
    rng = np.random.default_rng(5)
    for _ in range(8):
        r.add_request(rng.integers(0, 96, 6).astype(np.int32), 4)
    done = {}
    for _ in range(300):
        t[0] += 0.5
        for c in r.step():
            done[c.request_id] = c
        if not r.has_work:
            break
    assert len(done) == 8
    assert r.stats["scaleouts"] == 1
    assert r.replica_states()["r1"] == "live"
    # recovery: no traffic, SLO recovers, hold elapses -> drain back
    for _ in range(40):
        t[0] += 1.0
        r.step()
        if r.replica_states()["r1"] == "standby":
            break
    assert r.replica_states() == {"r0": "live", "r1": "standby"}
    assert r.stats["scaleins"] == 1


def test_failover_to_standby_without_slo(gpt, refs):
    """Total live-fleet loss admits the standby immediately — failover
    needs no SLO verdict — and the workload completes bitwise."""
    prompts, new, seqs = refs
    faults.clear()
    try:
        r = FleetRouter(gpt, replicas=1, replica_kwargs=KW, standby=1)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        r.step()
        faults.inject("router_replica_lost", "r0")
        done = r.run()
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    assert r.stats["deaths"] == 1
    assert r.replica_states() == {"r0": "dead", "r1": "live"}


def test_scaleout_stall_degrades_gracefully(gpt, metrics_on):
    """The router_scaleout_stall drill: a wedged standby admission is
    interrupted by the watchdog (coded PDT-E020 flight), counted as a
    scaleout failure, and the fleet keeps serving on the live
    replicas — no hang, no loss."""
    faults.clear()
    t = [0.0]
    try:
        r = FleetRouter(gpt, replicas=1, replica_kwargs=KW, standby=1,
                        fleet_slo=_breach_specs(), clock=lambda: t[0],
                        scaleout_timeout_ms=150.0)
        # EVERY admission attempt wedges (cooldown retries included)
        faults.inject("router_scaleout_stall", "r1", times=1000)
        rng = np.random.default_rng(5)
        rids = [r.add_request(rng.integers(0, 96, 6).astype(np.int32),
                              4) for _ in range(6)]
        done = {}
        for _ in range(300):
            t[0] += 0.5
            for c in r.step():
                done[c.request_id] = c
            if not r.has_work:
                break
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)       # served on the live rep
    assert r.stats["scaleout_failures"] >= 1
    assert r.stats["scaleouts"] == 0
    assert r.replica_states()["r1"] == "standby"
    assert wdog.armed() == []


# ============================================== metrics-off parity ==

def test_metrics_off_bitwise_noop(gpt, refs):
    """PDTPU_METRICS off: identical routing decisions, identical token
    streams, and the always-on ``stats`` counters still count (the
    engine contract extends to the fleet).  SLO judgment — and with it
    SLO-driven scaling — is off, exactly like the engines'."""
    prompts, new, seqs = refs
    old = paddle.get_flags("metrics")["metrics"]

    def drive():
        r = FleetRouter(gpt, replicas=2, replica_kwargs=KW)
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        return r, rids, r.run()

    try:
        paddle.set_flags({"metrics": True})
        r_on, rids_on, done_on = drive()
        paddle.set_flags({"metrics": False})
        r_off, rids_off, done_off = drive()
    finally:
        paddle.set_flags({"metrics": old})
    for a, b in zip(rids_on, rids_off):
        np.testing.assert_array_equal(done_on[a].sequence,
                                      done_off[b].sequence)
    san = lambda d: {k: v for k, v in d.items()}
    assert san(r_on.stats) == san(r_off.stats)
    for rid, ref in zip(rids_on, seqs):
        np.testing.assert_array_equal(done_on[rid].sequence, ref)


# ============================================= rpc-backed replica ==

def test_rpc_replica_loopback(gpt, refs):
    """One replica fronted by the rpc proxy (loopback worker): the
    fleet surface — placement, cached-prefix queries, stats — crosses
    the wire and the streams stay bitwise."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.inference import RpcReplica, register_replica_worker
    from paddle_tpu.inference.router import _REPLICA_WORKERS
    prompts, new, seqs = refs
    rpc.init_rpc("fleet_w0", rank=0, world_size=1)
    try:
        remote_eng = ContinuousBatchingEngine(gpt, **KW)
        register_replica_worker("fleet_w0", remote_eng)
        local_eng = ContinuousBatchingEngine(gpt, **KW)
        r = FleetRouter(replicas=[local_eng,
                                  RpcReplica(to="fleet_w0")])
        rids = [r.add_request(p, n) for p, n in zip(prompts, new)]
        done = r.run()
        assert sorted(done) == sorted(rids)
        for rid, ref in zip(rids, seqs):
            np.testing.assert_array_equal(done[rid].sequence, ref)
        # both sides actually served (the proxy carried real traffic)
        assert remote_eng.stats["admitted"] >= 1
        assert local_eng.stats["admitted"] >= 1
        assert remote_eng.stats["admitted"] + \
            local_eng.stats["admitted"] == len(prompts)
    finally:
        _REPLICA_WORKERS.clear()
        rpc.shutdown()


# ============================ requeue accounting (ISSUE 17 sat. 2) ==

def test_requeue_accounting_not_double_counted(gpt):
    """Regression: the ``engine_decode_worker_lost`` requeue used to
    re-count ``prefill_tokens_requested`` for the same logical request
    (inflating the prefill_saved_frac denominator).  Pinned counter
    pair on the forced-loss drill: the fault run's REQUESTED total
    equals the clean run's exactly — demand is counted once per
    logical request — while COMPUTED alone grows by the genuine
    recompute; on the clean run computed stays net of prefix-cache
    hits (computed == requested - cache_hit_tokens)."""
    prompts, new = _workload()

    def drive(fault):
        faults.clear()
        if fault:
            faults.inject("engine_decode_worker_lost", "*", times=1)
        try:
            srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                               decode_kwargs=dict(KW))
            rids = [srv.add_request(p, n)
                    for p, n in zip(prompts, new)]
            done = srv.run()
        finally:
            faults.clear()
        agg = lambda k: sum(e.stats[k] for e in srv.prefill_group)
        return (agg("prefill_tokens_requested"),
                agg("prefill_tokens_computed"),
                agg("cache_hit_tokens"), srv.stats["requeues"],
                rids, done)

    req_c, comp_c, hit_c, rq_c, rids_c, done_c = drive(False)
    req_f, comp_f, hit_f, rq_f, rids_f, done_f = drive(True)
    assert rq_c == 0 and rq_f >= 1          # the drill actually fired
    assert req_f == req_c                   # demand counted ONCE
    assert comp_c == req_c - hit_c          # computed net of hits
    assert comp_f > comp_c                  # recompute is real work
    for a, b in zip(rids_c, rids_f):        # ...and moved no tokens
        np.testing.assert_array_equal(done_c[a].sequence,
                                      done_f[b].sequence)


def test_router_requeue_demand_counted_once(gpt):
    """The same invariant through the ROUTER's requeue path: a killed
    replica's requests re-prefill on a survivor with ``requeue=True``,
    so the fleet-wide requested total matches the unfaulted run."""
    prompts, new = _workload(seed=2)

    def drive(kill):
        faults.clear()
        try:
            r = FleetRouter(gpt, replicas=2, replica_kwargs=KW)
            rids = [r.add_request(p, n)
                    for p, n in zip(prompts, new)]
            done, steps = {}, 0
            while r.has_work:
                if kill and steps == 2:
                    faults.inject("router_replica_lost", "r0")
                for c in r.step():
                    done[c.request_id] = c
                steps += 1
                assert steps < 2000
        finally:
            faults.clear()
        req = sum(rep.engine.stats["prefill_tokens_requested"]
                  for rep in r._replicas)
        return req, rids, done

    req_c, rids_c, done_c = drive(False)
    req_f, rids_f, done_f = drive(True)
    assert req_f == req_c
    for a, b in zip(rids_c, rids_f):
        np.testing.assert_array_equal(done_c[a].sequence,
                                      done_f[b].sequence)
