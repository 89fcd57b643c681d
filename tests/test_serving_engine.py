"""Continuous-batching serving engine (ISSUE 3 tentpole layer 2).

Correctness model: every request routed through the engine — whatever
the admission order, slot contention, prefill chunking, or page-table
shuffling — must produce EXACTLY the greedy sequence that a standalone
``generate(kv_cache='paged')`` call produces for the same prompt.
"""
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    # the session-scoped tiny model (tests/conftest.py): its compiled
    # program caches are shared with test_quant_serving.py
    return serving_gpt


# The file's engine geometries.  Each is a set of compiled programs
# (10-20 s under tier-1's load), so a case takes one of these three
# unless it says why not: two slots with room for every page, one slot
# (the cases that count what queues behind it), and three slots over a
# pool BELOW their working set (8 usable pages of 4 tokens where three
# requests of up to 16 need 12).
_STEP = dict(decode_window=4, prefill_chunk=8, q_block=2)
ROOMY = dict(max_slots=2, page_size=8, max_seq_len=32, **_STEP)
ONE_SLOT = dict(max_slots=1, page_size=8, max_seq_len=16, **_STEP)
CONTENDED = dict(max_slots=3, page_size=4, max_seq_len=16, total_pages=9,
                 **_STEP)


def _refs(model, prompts, new):
    return [generate(model, p[None, :], max_new_tokens=n).numpy()[0]
            for p, n in zip(prompts, new)]


def _assert_pool_conserved(eng, drained=True):
    """Allocator conservation: free, cached and in-use pages are
    disjoint, never include the null page, and sum to the usable pool.
    A DRAINED engine additionally has zero pages in use (retired pages
    may legitimately stay CACHED in the prefix index — the free list
    alone is no longer the whole story)."""
    st = eng.stats
    free = set(eng._free_pages)
    cached = set(eng._cache.cached_page_ids())
    assert len(eng._free_pages) == len(free)          # no duplicates
    assert not (free & cached)
    assert 0 not in free and 0 not in cached
    assert (st["pages_in_use"] + st["pages_free"]
            + st["cached_pages"]) == eng.total_pages - 1
    eng._cache.check()                                # PDT-E019 audit
    if drained:
        assert st["pages_in_use"] == 0
        assert free | cached == set(range(1, eng.total_pages))


def test_engine_matches_generate_with_slot_contention(gpt):
    """4 ragged requests through 2 slots: later requests are admitted
    MID-STREAM as earlier ones retire; mixed steps run admissions'
    prefill chunks ragged-batched with ongoing decodes; every output
    must equal the sequential generate() row."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12)]
    new = [6, 4, 7, 5]
    refs = _refs(gpt, prompts, new)
    eng = ContinuousBatchingEngine(gpt, **ROOMY)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    # continuous batching actually happened: more requests than slots,
    # and prefill ran ragged-batched with ongoing decodes
    assert eng.stats["admitted"] == 4 and eng.stats["retired"] == 4
    assert eng.stats["mixed_steps"] >= 2


def test_engine_page_reuse_and_free_list_restore(gpt):
    """Retired sequences return pages to the free list and later
    admissions REUSE them: total allocations exceed the peak resident
    count, and the free list is whole after the drain."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 96, (6,)).astype(np.int32)
               for _ in range(4)]
    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT)
    refs = _refs(gpt, prompts, [4] * 4)
    rids = [eng.add_request(p, 4) for p in prompts]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = eng.stats
    assert st["pages_allocated"] > st["peak_pages_in_use"]  # reuse
    _assert_pool_conserved(eng)          # nothing leaked, nothing dup'd
    assert st["peak_pages_in_use"] <= 2  # one slot's worst case
    # health gauges: a drained engine holds no pages in use (retired
    # full pages may stay CACHED in the prefix index by design)
    assert st["pages_in_use"] == 0
    assert st["pages_free"] + st["cached_pages"] == eng.total_pages - 1
    assert st["queue_depth"] == 0
    # ... and a loaded engine reads loaded: queue 3 deep behind slot 0
    eng.add_request(prompts[0], 4)
    for p in prompts[1:]:
        eng.add_request(p, 4)
    eng.step()
    st = eng.stats
    assert st["queue_depth"] == 3 and st["pages_in_use"] > 0
    assert st["pages_free"] == (eng.total_pages - 1
                                - st["pages_in_use"]
                                - st["cached_pages"])
    eng.run()
    assert eng.stats["pages_in_use"] == 0
    _assert_pool_conserved(eng)


def test_engine_eos_early_retire(gpt):
    """eos stops a request early (device stop rule == host replay) and
    frees its slot for the queue."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 96, (5,)).astype(np.int32)
    full = generate(gpt, prompt[None, :], max_new_tokens=8).numpy()[0]
    eos = int(full[prompt.size + 1])       # 2nd generated token
    ref = generate(gpt, prompt[None, :], max_new_tokens=8,
                   eos_token_id=eos).numpy()[0]
    eng = ContinuousBatchingEngine(gpt, **ROOMY)
    rid = eng.add_request(prompt, 8, eos_token_id=eos)
    done = eng.run()
    got = done[rid].sequence
    assert got[-1] == eos and got.size < prompt.size + 8  # stopped early
    np.testing.assert_array_equal(got, ref[:got.size])
    _assert_pool_conserved(eng)


def test_engine_llama_gqa(serving_llama_gqa):
    m = serving_llama_gqa
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (7, 4, 11)]
    new = [5, 6, 4]
    refs = _refs(m, prompts, new)
    # a geometry of its own: another model shares no program anyway,
    # and ``pages_per_block`` must be seen to reach the kernel
    eng = ContinuousBatchingEngine(m, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=3,
                                   prefill_chunk=6, q_block=2,
                                   pages_per_block=1)  # override threads
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)


def test_engine_rejects_oversize_request(gpt):
    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT)
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.add_request(np.zeros(12, np.int32), 8)


# ----------------------------------------------------------------------
# Overload / resilience (ISSUE 5): the engine must degrade gracefully —
# preempt-and-requeue under page pressure, coded rejections, deadlines,
# cancellation, a per-request decode guard, retried dispatches — while
# every SURVIVING request stays bit-identical to an uncontended
# generate(kv_cache='paged') run and no page ever leaks.
# ----------------------------------------------------------------------

def _paged_refs(model, prompts, new):
    return [generate(model, p[None, :], max_new_tokens=n,
                     kv_cache="paged").numpy()[0]
            for p, n in zip(prompts, new)]


def test_engine_preempt_requeue_bitwise(gpt):
    """Pool sized BELOW the working set: growth preempts the
    latest-admitted victim, which requeues and re-prefills
    prompt + tokens_so_far.  All requests complete, outputs are
    bitwise-identical to the uncontended run, zero pages leak, and the
    old pool-exhaustion RuntimeError is unreachable."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (6, 8, 5, 7)]
    new = [8, 7, 8, 6]
    refs = _paged_refs(gpt, prompts, new)
    # each request needs <= 4 pages (<= 16 tokens, page_size 4); three
    # slots' worst case is 12 pages but the pool only holds 8 usable
    eng = ContinuousBatchingEngine(gpt, **CONTENDED)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    assert sorted(done) == sorted(rids)
    for rid, ref in zip(rids, refs):
        assert done[rid].finish_reason == "length"
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = eng.stats
    assert st["preemptions"] > 0          # contention actually happened
    assert st["pages_in_use"] == 0        # zero leaked
    _assert_pool_conserved(eng)           # free+cached = the whole pool


def test_engine_serving_fault_drill(gpt):
    """The deterministic serving drill: oversubscribed pool, an
    injected dispatch transient (absorbed by bounded retry), an
    injected NaN decode (fails exactly one request), one cancel and one
    deadline expiry — survivors bit-identical, free list restored."""
    from paddle_tpu.core import errors
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (6, 7, 5, 8, 6)]
    new = [8, 6, 8, 7, 6]
    refs = _paged_refs(gpt, prompts, new)
    clock = [0.0]
    faults.clear()
    try:
        eng = ContinuousBatchingEngine(gpt, **CONTENDED,
                                       clock=lambda: clock[0])
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        r_nan, r_cancel = rids[1], rids[2]
        r_dead = eng.add_request(prompts[0], 8, deadline_ms=100.0)
        faults.inject("engine_dispatch", times=2)       # transient x2
        faults.inject("engine_nan_decode", match=str(r_nan))
        assert eng.cancel(r_cancel) and not eng.cancel(10_000)
        done = {c.request_id: c for c in eng.step()}
        clock[0] = 0.2                    # past r_dead's 100ms deadline
        done.update(eng.run())
        assert sorted(done) == sorted(rids + [r_dead])
        # exactly one guard failure, carrying the coded error
        assert done[r_nan].finish_reason == "failed"
        assert isinstance(done[r_nan].error, errors.NonFiniteLogitsError)
        assert done[r_nan].error.error_code == "PDT-E018"
        assert done[r_cancel].finish_reason == "cancelled"
        assert done[r_dead].finish_reason == "timeout"
        # survivors (co-resident with every fault above) are bitwise
        survivors = [r for r in rids if r not in (r_nan, r_cancel)]
        for rid, ref in zip(rids, refs):
            if rid in survivors:
                assert done[rid].finish_reason == "length"
                np.testing.assert_array_equal(done[rid].sequence, ref)
        st = eng.stats
        assert st["retries"] == 2         # transient absorbed, not fatal
        assert st["failed"] == 1 and st["cancelled"] == 1
        assert st["timeouts"] == 1
        assert st["pages_in_use"] == 0 and st["queue_depth"] == 0
        _assert_pool_conserved(eng)
    finally:
        faults.clear()


def test_engine_injected_page_pressure(gpt):
    """The engine_page_pressure site forces the preempt path with a
    roomy pool: the grower's victim requeues, recomputes, and both
    outputs stay bitwise."""
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(5)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    ref1, ref2 = _paged_refs(gpt, [p1, p2], [8, 8])
    faults.clear()
    try:
        eng = ContinuousBatchingEngine(gpt, **ROOMY)
        r1 = eng.add_request(p1, 8)
        r2 = eng.add_request(p2, 8)
        faults.inject("engine_page_pressure", match=str(r1))
        done = eng.run()
        np.testing.assert_array_equal(done[r1].sequence, ref1)
        np.testing.assert_array_equal(done[r2].sequence, ref2)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["pages_in_use"] == 0
    finally:
        faults.clear()


def test_engine_nan_decode_mid_stream(gpt):
    """Guard fires mid-DECODE (not at prefill): the failed request
    keeps its pre-fault tokens, the co-resident request's stream is
    untouched."""
    from paddle_tpu.core import errors
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(13)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    (ref2,) = _paged_refs(gpt, [p2], [8])
    faults.clear()
    try:
        eng = ContinuousBatchingEngine(gpt, **ROOMY)
        r1 = eng.add_request(p1, 8)
        r2 = eng.add_request(p2, 8)
        # at=2: first guarded dispatch for r1 is its prefill step; the
        # second poisons a decode window mid-stream
        faults.inject("engine_nan_decode", match=str(r1), at=2)
        done = eng.run()
        assert done[r1].finish_reason == "failed"
        assert isinstance(done[r1].error, errors.NonFiniteLogitsError)
        assert 0 < done[r1].tokens.size < 8   # partial stream survives
        assert done[r2].finish_reason == "length"
        np.testing.assert_array_equal(done[r2].sequence, ref2)
        assert eng.stats["failed"] == 1
    finally:
        faults.clear()


def test_engine_page_budget_eager_reject(gpt):
    """A request that can NEVER fit the pool is rejected at
    add_request with the coded PageBudgetError — not queued to crash
    step() later — and an admissible mix can never reach the step-time
    backstop."""
    from paddle_tpu.core import errors

    # a geometry of its own: a request the sequence limit admits (24 of
    # 32 tokens) over a pool that can never hold it (2 usable pages)
    eng = ContinuousBatchingEngine(gpt, max_slots=1, page_size=8,
                                   max_seq_len=32, total_pages=3, **_STEP)
    with pytest.raises(errors.PageBudgetError,
                       match="PDT-E016") as ei:
        eng.add_request(np.zeros(12, np.int32), 12)   # 3 pages > 2
    assert ei.value.error_code == "PDT-E016"
    assert eng.stats["rejected"] == 1
    assert not eng.has_work                   # nothing poisoned a queue
    # boundary: exactly the usable pool is admissible
    rid = eng.add_request(np.zeros(10, np.int32), 6)  # 16 tok = 2 pages
    done = eng.run()
    assert done[rid].finish_reason == "length"


def test_engine_queue_policies(gpt):
    """Bounded admission: 'reject' raises the coded QueueFullError,
    'block' steps the engine until the queue drains."""
    from paddle_tpu.core import errors

    rng = np.random.default_rng(17)
    p = rng.integers(0, 96, (5,)).astype(np.int32)
    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT, max_queue=1,
                                   queue_policy="reject")
    eng.add_request(p, 4)
    with pytest.raises(errors.QueueFullError, match="PDT-E017") as ei:
        eng.add_request(p, 4)             # queue full before any step
    assert ei.value.error_code == "PDT-E017"
    assert eng.stats["rejected"] == 1
    eng.run()

    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT, max_queue=1,
                                   queue_policy="block")
    rids = [eng.add_request(p, 4) for _ in range(3)]  # adds 2+ block
    done = eng.run()
    assert sorted(done) == sorted(rids)
    assert all(done[r].ok for r in rids)
    with pytest.raises(ValueError, match="queue_policy"):
        ContinuousBatchingEngine(gpt, queue_policy="drop")


def test_engine_run_budget_warns_and_surfaces_pending(gpt):
    """run(max_steps=...) exhausting its budget with work in flight
    warns (instead of returning silently like success) and
    pending_requests() names the stragglers."""
    rng = np.random.default_rng(19)
    prompts = [rng.integers(0, 96, (6,)).astype(np.int32)
               for _ in range(3)]
    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT)
    rids = [eng.add_request(p, 4) for p in prompts]
    with pytest.warns(RuntimeWarning, match="pending_requests"):
        done = eng.run(max_steps=2)
    pend = eng.pending_requests()
    assert pend and set(pend) == set(rids) - set(done)
    done.update(eng.run())                # budget off: drains clean
    assert sorted(done) == sorted(rids) and not eng.pending_requests()


def test_engine_cancel_after_final_token_honored(gpt):
    """cancel() racing retirement: the slot has already generated its
    final token (done, awaiting the next step boundary) when cancel()
    returns True — the promised "cancelled" result must surface, not a
    "length" retirement that silently outruns the cancellation."""
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, 96, (6,)).astype(np.int32)
    eng = ContinuousBatchingEngine(gpt, **ONE_SLOT)
    rid = eng.add_request(prompt, 4)
    done = {}
    for _ in range(50):
        if any(s.req is not None and s.done for s in eng._slots):
            break
        done.update(eng.step())
    else:
        pytest.fail("slot never reached done-awaiting-retirement")
    assert not done                       # nothing surfaced yet
    assert eng.cancel(rid)                # promises a "cancelled" result
    done.update(eng.run())
    assert done[rid].finish_reason == "cancelled"
    assert eng.stats["cancelled"] == 1 and eng.stats["retired"] == 0
    assert eng.stats["pages_in_use"] == 0
