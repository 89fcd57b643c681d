"""Disaggregated prefill/decode + tensor-parallel serving (ISSUE 13).

Acceptance model: a TP-sharded engine (``mesh=``/``tp_axis=``) and a
``DisaggServer`` prefill->handoff->decode run must both produce EXACTLY
the greedy token streams of the single-device colocated engine — TP is
a layout, disaggregation a transport; neither may move a token — with
the allocator's pool conservation holding on every engine involved.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine, DisaggServer,
                                  KVPageTransport)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.resilience import faults

from test_serving_engine import _assert_pool_conserved

# ONE geometry for the whole module (matches test_serving_engine's, so
# single-device programs come off the session model's cache; the TP
# programs cache on the model per (geometry, mesh) too, so every test
# here reuses the first one's compiles)
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


@pytest.fixture(scope="module")
def mesh4():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:4]), ("tp",))


def _workload(seed=0, sizes=(5, 9, 3, 12), new=(6, 4, 7, 5)):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, 96, (n,)).astype(np.int32)
             for n in sizes], list(new))


def _drive(model, mesh=None, prompts=None, new=None, **kw):
    eng = ContinuousBatchingEngine(model, mesh=mesh, **{**KW, **kw})
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    return [done[r].sequence for r in rids], eng


@pytest.fixture(scope="module")
def refs(gpt):
    """Single-device engine streams for the shared workload — the bar
    every TP/disagg variant must hit bitwise."""
    prompts, new = _workload()
    seqs, eng = _drive(gpt, None, prompts, new)
    _assert_pool_conserved(eng)
    return prompts, new, seqs


# ======================================================== TP engine ==

def test_tp2_matches_single_device_slot_contention(gpt, mesh2, refs):
    """4 ragged requests through 2 slots on a TP=2 mesh: admission,
    chunked prefill, decode windows and retirement all run over
    head-sharded pools with one psum per layer pair — token streams
    must be EXACTLY the single-device engine's."""
    prompts, new, seqs = refs
    out, eng = _drive(gpt, mesh2, prompts, new)
    for a, b in zip(out, seqs):
        np.testing.assert_array_equal(a, b)
    assert eng.tp == 2
    _assert_pool_conserved(eng)


def test_tp2_shared_prefix_and_cow(gpt, mesh2):
    """Prefix cache + COW on sharded pools: same-prefix twins map the
    radix index over TP pools (the COW page copy is one donated
    sharded dispatch) — bitwise vs the single-device engine, with
    cache hits actually happening."""
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 96, (16,)).astype(np.int32)
    tail = rng.integers(0, 96, (3,)).astype(np.int32)
    prompts = [shared, shared,                      # full-page COW hit
               np.concatenate([shared[:8], tail])]  # partial hit
    new = [4, 4, 4]

    def run(mesh):
        eng = ContinuousBatchingEngine(gpt, mesh=mesh, **KW)
        r0 = eng.add_request(prompts[0], new[0])
        first = eng.run()                 # publish, then hit the cache
        rs = [eng.add_request(p, n)
              for p, n in zip(prompts[1:], new[1:])]
        done = eng.run()
        seqs = [first[r0].sequence] + [done[r].sequence for r in rs]
        return seqs, eng

    ref, _ = run(None)
    out, eng = run(mesh2)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
    assert eng.stats["cache_hits"] >= 2
    _assert_pool_conserved(eng)


def test_tp2_kv_quant(gpt, mesh2, refs):
    """int8 KV pages under TP: data AND scale side-pools shard by
    kv-head; per-(head, slot) absmax quantization is head-local, so
    quantized bytes match the single-device engine's and greedy
    streams are token-identical."""
    prompts, new, _ = refs
    ref, _ = _drive(gpt, None, prompts, new, kv_quant=True)
    out, eng = _drive(gpt, mesh2, prompts, new, kv_quant=True)
    for a, b in zip(out, ref):
        np.testing.assert_array_equal(a, b)
    assert eng.kv_quant
    _assert_pool_conserved(eng)


def test_tp2_spec_decode(gpt, mesh2, refs):
    """Speculative decoding through the TP verify program (n-gram
    proposer): greedy spec on a TP mesh is bitwise vs BOTH the
    single-device spec engine and the plain stream."""
    prompts, new, seqs = refs
    out, eng = _drive(gpt, mesh2, prompts, new, spec_decode=True,
                      spec_k=3)
    for a, b in zip(out, seqs):
        np.testing.assert_array_equal(a, b)
    assert eng.stats["spec_accepted"] >= 0  # counters wired
    assert eng.stats["decode_dispatches"] > 0
    _assert_pool_conserved(eng)


def test_tp_llama_gqa_both_regimes(serving_llama_gqa, mesh2, mesh4):
    """GQA awareness: Hk=2 heads shard over tp=2 (Hk % tp == 0) and
    REPLICATE over tp=4 (each pair of shards attends a 1-head slice
    of the replicated pools) — both bitwise vs single-device."""
    m = serving_llama_gqa
    prompts, new = _workload(seed=3, sizes=(7, 4, 11), new=(5, 6, 4))
    ref, _ = _drive(m, None, prompts, new)
    for mesh in (mesh2, mesh4):
        out, eng = _drive(m, mesh, prompts, new)
        for a, b in zip(out, ref):
            np.testing.assert_array_equal(a, b)
        assert eng._tpp.meta["shard_kv"] == (mesh is mesh2)
        _assert_pool_conserved(eng)


def test_tp_validation(gpt, mesh2):
    """Head counts the Megatron cut cannot serve fail EAGERLY with a
    clear error, and a multi-axis mesh demands an explicit tp_axis."""
    import jax
    from jax.sharding import Mesh
    paddle.seed(0)
    bad = LlamaForCausalLM(LlamaConfig(
        vocab_size=96, hidden_size=48, num_layers=1, num_heads=3,
        num_kv_heads=3, max_seq_len=64))
    bad.eval()
    with pytest.raises(ValueError, match="num_heads"):
        ContinuousBatchingEngine(bad, mesh=mesh2, **KW)
    two_axis = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                    ("a", "b"))
    with pytest.raises(ValueError, match="tp_axis"):
        ContinuousBatchingEngine(gpt, mesh=two_axis, **KW)


# ================================================= pool export/import ==

def test_export_import_roundtrip(gpt, refs):
    """Engine-level handoff substrate: prefill on one engine, export
    at the first token, import into a FRESH engine, finish decoding
    there — the stitched stream is bitwise the uninterrupted one, and
    both pools conserve."""
    prompts, new, seqs = refs
    src = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[0], 1)
    src.run()                      # slot retires after its one token —
    # re-admit and step until the first token is resident instead
    src2 = ContinuousBatchingEngine(gpt, **KW)
    rid = src2.add_request(prompts[0], 1)
    payload = None
    for _ in range(100):
        src2.step()
        try:
            payload = src2.export_request(rid)
            break
        except (KeyError, ValueError):
            continue
    assert payload is not None
    dst = ContinuousBatchingEngine(gpt, **KW)
    got = dst.import_request(payload, new[0])
    assert got == rid
    done = dst.run()
    np.testing.assert_array_equal(done[rid].sequence, seqs[0])
    src2.run()
    _assert_pool_conserved(src2)
    _assert_pool_conserved(dst)
    # layout validation: mismatched page_size must refuse
    other = ContinuousBatchingEngine(gpt, **{**KW, "page_size": 16,
                                             "max_seq_len": 32})
    with pytest.raises(ValueError, match="page_size"):
        other.import_request(payload, new[0])


# ======================================================= DisaggServer ==

def _disagg_run(gpt, prompts, new, **srv_kw):
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW), **srv_kw)
    rids = [srv.add_request(p, n) for p, n in zip(prompts, new)]
    done = srv.run()
    return [done[r] for r in rids], srv


def test_disagg_bitwise_vs_colocated(gpt, refs):
    """The acceptance run: prefill group -> KV-page handoff -> decode
    group, bitwise vs the colocated engine, pool conservation holding
    on BOTH groups after the drain."""
    prompts, new, seqs = refs
    out, srv = _disagg_run(gpt, prompts, new)
    for c, ref in zip(out, seqs):
        np.testing.assert_array_equal(c.sequence, ref)
        assert c.ok
    st = srv.stats
    assert st["handoffs"] == len(prompts)
    assert st["handoff_bytes"] > 0
    for eng in srv.prefill_group + srv.decode_group:
        _assert_pool_conserved(eng)
    # handoff observability: histogram counted every transfer
    node = srv.metrics()["serving"]["handoff_ms"]
    assert node["count"] == len(prompts)


def test_disagg_handoff_transient_drill(gpt, refs):
    """Two injected ConnectionErrors on the transport are absorbed by
    the bounded retry; outputs stay bitwise and the retry counter
    records exactly two."""
    prompts, new, seqs = refs
    faults.clear()
    faults.inject("engine_handoff_transient", "*", times=2)
    try:
        out, srv = _disagg_run(gpt, prompts, new)
    finally:
        faults.clear()
    for c, ref in zip(out, seqs):
        np.testing.assert_array_equal(c.sequence, ref)
    assert srv.stats["handoff_retries"] == 2
    assert srv.stats["handoffs"] == len(prompts)


def test_disagg_decode_worker_lost_drill(gpt, refs):
    """A decode worker lost at handoff time: the payload is discarded,
    the request requeues to the prefill group and re-prefills from
    token zero — outputs bitwise, only ``requeues`` moves."""
    prompts, new, seqs = refs
    faults.clear()
    faults.inject("engine_decode_worker_lost", "1", times=1)
    try:
        out, srv = _disagg_run(gpt, prompts, new)
    finally:
        faults.clear()
    for c, ref in zip(out, seqs):
        np.testing.assert_array_equal(c.sequence, ref)
    assert srv.stats["requeues"] == 1
    req = srv._reqs[1]
    assert req.requeues == 1
    for eng in srv.prefill_group + srv.decode_group:
        _assert_pool_conserved(eng)


def test_disagg_eos_at_first_token(gpt, refs):
    """An eos produced by the prefill itself completes on the prefill
    side — no handoff ships, the result is reason='stop'."""
    prompts, new, seqs = refs
    eos = int(seqs[0][prompts[0].size])       # its first generated tok
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW))
    rid = srv.add_request(prompts[0], new[0], eos_token_id=eos)
    done = srv.run()
    assert done[rid].finish_reason == "stop"
    np.testing.assert_array_equal(done[rid].tokens, [eos])
    assert srv.stats["handoffs"] == 0


def test_disagg_prefix_cache_survives_handoff(gpt):
    """Decode-side publish: after the first request retires on the
    decode group, a second identical-prompt request's import RETAINS
    the decode cache's pages instead of re-scattering, and the
    prefill side's own cache cuts its recomputed prefill tokens."""
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, 96, (16,)).astype(np.int32)
    ref, _ = _drive(gpt, None, [prompt], [4])
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW))
    r1 = srv.add_request(prompt, 4)
    d1 = srv.run()
    r2 = srv.add_request(prompt, 4)
    d2 = srv.run()
    np.testing.assert_array_equal(d1[r1].sequence, ref[0])
    np.testing.assert_array_equal(d2[r2].sequence, ref[0])
    dec = srv.decode_group[0]
    pre = srv.prefill_group[0]
    assert dec.stats["cache_hits"] >= 1           # import retained
    assert pre.stats["cache_hits"] >= 1           # prefill-side reuse
    assert pre.stats["prefill_tokens_computed"] \
        < pre.stats["prefill_tokens_requested"]
    for eng in (pre, dec):
        _assert_pool_conserved(eng)


def test_disagg_tp_decode_group(gpt, mesh2, refs):
    """Groups compose with TP: a single-device prefill group handing
    off to a TP=2-sharded decode group stays bitwise (the payload is
    layout-neutral — import scatters into sharded pools)."""
    prompts, new, seqs = refs
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs={**KW, "mesh": mesh2})
    rids = [srv.add_request(p, n) for p, n in zip(prompts, new)]
    done = srv.run()
    for r, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[r].sequence, ref)
    assert srv.decode_group[0].tp == 2
    for eng in srv.prefill_group + srv.decode_group:
        _assert_pool_conserved(eng)


def test_disagg_rpc_transport(gpt, refs):
    """The handoff bytes cross a real rpc agent (loopback worker):
    same payload, same retry envelope, bitwise output."""
    from paddle_tpu.distributed import rpc
    from paddle_tpu.inference import register_decode_worker
    prompts, new, seqs = refs
    rpc.init_rpc("disagg_w0", rank=0, world_size=1)
    try:
        srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                           decode_kwargs=dict(KW),
                           transport=KVPageTransport(to="disagg_w0"))
        register_decode_worker("disagg_w0", srv.decode_group[0])
        rids = [srv.add_request(p, n) for p, n in zip(prompts, new)]
        done = srv.run()
        for r, ref in zip(rids, seqs):
            np.testing.assert_array_equal(done[r].sequence, ref)
        assert srv.stats["handoffs"] == len(prompts)
    finally:
        rpc.shutdown()


def test_disagg_worker_lost_two_prefill_workers(gpt, refs):
    """Worker-lost requeue with prefill_workers=2: the in-flight
    guard unions BOTH engines, so the requeued rid cannot be
    double-admitted on the other worker while its old slot drains
    (review regression: a truncated duplicate 1-token result)."""
    prompts, new, seqs = refs
    faults.clear()
    faults.inject("engine_decode_worker_lost", "*", times=1)
    try:
        srv = DisaggServer(gpt, prefill_workers=2,
                           prefill_kwargs=dict(KW),
                           decode_kwargs=dict(KW))
        rids = [srv.add_request(p, n) for p, n in zip(prompts, new)]
        done = srv.run()
    finally:
        faults.clear()
    assert sorted(done) == sorted(rids)         # no duplicates/losses
    for r, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[r].sequence, ref)
        assert done[r].ok
    assert srv.stats["requeues"] == 1


def test_disagg_single_token_budget(gpt, refs):
    """max_new_tokens=1: the prefill result IS the final result — no
    handoff ships, and the one token matches the colocated engine's
    (review regression: this used to crash import_request with
    'request already complete')."""
    prompts, new, seqs = refs
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW))
    rid = srv.add_request(prompts[0], 1)
    done = srv.run()
    np.testing.assert_array_equal(done[rid].tokens,
                                  seqs[0][prompts[0].size:
                                          prompts[0].size + 1])
    assert done[rid].finish_reason == "length"
    assert srv.stats["handoffs"] == 0


def test_disagg_oversize_rejected_eagerly(gpt):
    """A request the DECODE group can never hold fails at
    add_request, not mid-handoff (review regression: the prefill
    group's 1-token budget used to let it admit and crash step())."""
    from paddle_tpu.core.errors import PageBudgetError
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW))
    with pytest.raises(ValueError, match="decode-group max_seq_len"):
        srv.add_request(np.zeros(8, np.int32), 100)
    small = {**KW, "total_pages": 3}
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=small)
    with pytest.raises(PageBudgetError):
        srv.add_request(np.zeros(8, np.int32), 20)


def test_disagg_deadline_spans_handoff(gpt, refs):
    """The deadline is ONE budget armed at coordinator admission:
    a request whose TTL expires while parked between prefill and
    decode times out instead of getting a fresh deadline on the
    decode side (review regression)."""
    prompts, new, _ = refs
    t = [0.0]
    clock = lambda: t[0]
    srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                       decode_kwargs=dict(KW), clock=clock)
    rid = srv.add_request(prompts[0], new[0], deadline_ms=50.0)
    # run prefill up to the export, then let the clock blow the TTL
    # while the payload sits in the handoff queue
    for _ in range(50):
        srv._submit_pending()
        for eng in srv.prefill_group:
            eng.step()
            srv._export_first_tokens(eng)
        if srv._ready:
            break
    assert srv._ready, "first token never exported"
    t[0] = 1.0                                  # 1000 ms >> 50 ms TTL
    done = srv.run()
    assert done[rid].finish_reason == "timeout"
    assert srv.stats["handoffs"] == 0


def test_disagg_handoff_retries_exhausted_keeps_payloads(gpt, refs):
    """A handoff whose transient never clears raises out of step()
    after the bounded retries — but the payload (and every other
    parked payload) stays in the handoff queue, so clearing the fault
    and stepping again completes everything (review regression: the
    queue used to be lost mid-loop)."""
    prompts, new, seqs = refs
    faults.clear()
    faults.inject("engine_handoff_transient", "*", times=0)  # forever
    try:
        srv = DisaggServer(gpt, prefill_kwargs=dict(KW),
                           decode_kwargs=dict(KW))
        rids = [srv.add_request(p, n) for p, n in zip(prompts, new)]
        with pytest.raises(ConnectionError):
            for _ in range(100):
                srv.step()
        assert srv._ready, "failed payload must stay queued"
    finally:
        faults.clear()
    done = srv.run()                        # fault gone: self-heals
    for r, ref in zip(rids, seqs):
        np.testing.assert_array_equal(done[r].sequence, ref)


def test_disagg_prefill_pool_validated_eagerly(gpt):
    """A prompt the PREFILL pool can never hold fails at add_request
    instead of poisoning _submit_pending forever (review
    regression)."""
    from paddle_tpu.core.errors import PageBudgetError
    srv = DisaggServer(gpt,
                       prefill_kwargs={**KW, "total_pages": 2},
                       decode_kwargs=dict(KW))
    with pytest.raises(PageBudgetError):
        srv.add_request(np.zeros(16, np.int32), 4)
    # and the server still serves admissible requests afterwards
    rid = srv.add_request(np.zeros(4, np.int32), 2)
    assert rid in srv.run()


def test_import_failure_releases_pages(gpt, refs):
    """An import whose scatter dispatch exhausts its retries releases
    every acquired/retained page before propagating — repeated caller
    retries must not drain the pool (review regression)."""
    prompts, new, _ = refs
    src = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[1], 1)
    payload = None
    for _ in range(100):
        src.step()
        try:
            payload = src.export_request(rid)
            break
        except (KeyError, ValueError):
            continue
    assert payload is not None
    dst = ContinuousBatchingEngine(gpt, **KW, dispatch_retries=0)
    faults.clear()
    faults.inject("engine_dispatch", "import", times=0)   # every time
    try:
        for _ in range(3):                  # caller retry loop
            with pytest.raises(ConnectionError):
                dst.import_request(payload, new[1])
    finally:
        faults.clear()
    _assert_pool_conserved(dst)             # nothing leaked
    # fault gone: the same import now succeeds and decodes bitwise
    got = dst.import_request(payload, new[1])
    assert got == rid
    src.run()


def test_import_advances_auto_rid(gpt, refs):
    """An imported integer rid advances the auto counter so a later
    request_id=None add_request cannot collide with the resident
    import (review regression)."""
    prompts, new, _ = refs
    src = ContinuousBatchingEngine(gpt, **KW)
    rid = src.add_request(prompts[2], 1, request_id=5)
    payload = None
    for _ in range(100):
        src.step()
        try:
            payload = src.export_request(rid)
            break
        except (KeyError, ValueError):
            continue
    dst = ContinuousBatchingEngine(gpt, **KW)
    assert dst.import_request(payload, new[2]) == 5
    auto = dst.add_request(prompts[0], 2)
    assert auto == 6                        # not 0..5
    src.run()
    dst.run()
    _assert_pool_conserved(dst)
