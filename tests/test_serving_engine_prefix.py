"""Continuous-batching serving engine, the cross-request KV prefix cache
(ISSUE 6).  The other half of tests/test_serving_engine.py, whose
correctness model this keeps: a file of its own where it shares no fixture
but the session's models, so that neither is over 200 s of tier-1.
"""
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine
from test_serving_engine import _assert_pool_conserved, _paged_refs


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    return serving_gpt     # session tiny model (tests/conftest.py)


# ----------------------------------------------------------------------
# Cross-request KV prefix cache (ISSUE 6): a radix index over the page
# pool maps shared prefixes onto already-written pages (block-table
# indirection only), with copy-on-write at the divergence page and LRU
# eviction — bitwise-identical to generate(kv_cache='paged') and to the
# cache-off engine in every mix, including preempt-requeue restore and
# post-eviction re-admission.
# ----------------------------------------------------------------------

def _engine(gpt, **kw):
    args = dict(max_slots=2, page_size=4, max_seq_len=32,
                decode_window=4, prefill_chunk=8, q_block=2)
    args.update(kw)
    return ContinuousBatchingEngine(gpt, **args)


def test_engine_prefix_cache_shared_prefix_bitwise(serving_lm):
    """Requests sharing a long prompt prefix: later admissions map the
    shared pages from the index (prefill tokens computed drops below
    tokens requested) and every output is bitwise-identical to the
    uncached reference AND to a cache-off engine."""
    rng = np.random.default_rng(29)
    shared = rng.integers(0, 96, (12,)).astype(np.int32)  # 3 full pages
    tails = [rng.integers(0, 96, (n,)).astype(np.int32)
             for n in (3, 2, 5, 1)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    new = [6, 5, 4, 6]
    refs = _paged_refs(serving_lm, prompts, new)

    outs = {}
    for mode in (True, False):
        eng = _engine(serving_lm, prefix_cache=mode)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        done = eng.run()
        outs[mode] = [done[r].sequence for r in rids]
        st = eng.stats
        if mode:
            # the first two admissions run concurrently (2 slots) and
            # prefill the shared prefix independently; both later
            # admissions hit the published pages
            assert st["cache_hits"] >= 2
            assert st["cache_hit_tokens"] >= 2 * 12
            assert (st["prefill_tokens_computed"]
                    < st["prefill_tokens_requested"])
            _assert_pool_conserved(eng)
        else:
            # cache off restores the uncached meter exactly
            assert st["cache_hits"] == 0 and st["cached_pages"] == 0
            assert (st["prefill_tokens_computed"]
                    == st["prefill_tokens_requested"])
            assert len(eng._free_pages) == eng.total_pages - 1
    for got_on, got_off, ref in zip(outs[True], outs[False], refs):
        np.testing.assert_array_equal(got_on, ref)
        np.testing.assert_array_equal(got_off, ref)


def test_engine_prefix_cache_cow_full_prompt(serving_lm):
    """A fully-cached page-aligned prompt takes the copy-on-write
    path: the divergence page is duplicated, exactly ONE token is
    recomputed for the last position's logits, the shared page is
    never written, and the output stays bitwise."""
    rng = np.random.default_rng(31)
    prompt = rng.integers(0, 96, (8,)).astype(np.int32)  # 2 full pages
    (ref,) = _paged_refs(serving_lm, [prompt], [6])
    eng = _engine(serving_lm)
    r1 = eng.add_request(prompt, 6)
    done = eng.run()
    np.testing.assert_array_equal(done[r1].sequence, ref)
    # retirement published the full prompt pages
    assert eng.stats["cached_pages"] >= 2
    base = eng.stats["prefill_tokens_computed"]
    r2 = eng.add_request(prompt, 6)           # identical prompt: full hit
    done = eng.run()
    np.testing.assert_array_equal(done[r2].sequence, ref)
    st = eng.stats
    assert st["cache_hit_tokens"] >= prompt.size - 1   # COW: all but one
    assert st["prefill_tokens_computed"] - base == 1   # 1 recomputed tok
    _assert_pool_conserved(eng)


def test_engine_preempt_requeue_recompute_drop(gpt):
    """The PR5 recompute gap, closed: a preempted victim's pages are
    PUBLISHED to the index (not freed), so its re-admission restores
    from its own just-published pages — prefill-tokens-computed drops
    versus the cache-off engine on the identical forced-preemption
    workload, outputs bitwise both ways.  (In a truly starved pool the
    LRU may reclaim some of the victim's pages for the grower — that
    path is covered by test_engine_preempt_requeue_bitwise; here the
    pool is roomy and the ``engine_page_pressure`` drill forces the
    preemption, so the published pages survive to the re-admission.)"""
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(41)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    refs = _paged_refs(gpt, [p1, p2], [8, 8])
    computed = {}
    faults.clear()
    try:
        for mode in (False, True):
            eng = _engine(gpt, prefix_cache=mode)
            r1 = eng.add_request(p1, 8)
            r2 = eng.add_request(p2, 8)
            # r1's growth hits injected pressure -> r2 (latest) preempts
            faults.inject("engine_page_pressure", match=str(r1))
            done = eng.run()
            np.testing.assert_array_equal(done[r1].sequence, refs[0])
            np.testing.assert_array_equal(done[r2].sequence, refs[1])
            st = eng.stats
            assert st["preemptions"] >= 1
            computed[mode] = st["prefill_tokens_computed"]
            if mode:
                # prompts are DISTINCT, so every hit is the victim's
                # re-admission restoring from its own published pages
                assert st["cache_hits"] >= 1
                assert st["evictions"] == 0    # roomy pool: none lost
                _assert_pool_conserved(eng)
            else:
                assert st["cache_hits"] == 0
    finally:
        faults.clear()
    assert computed[True] < computed[False]


def test_engine_cache_evict_drill_bitwise(gpt):
    """The deterministic engine_cache_evict drill: cached prefix pages
    are evicted under the injected pressure, and a re-admission of the
    evicted prefix transparently re-prefills with bitwise-identical
    output (the cache can only ever cost recompute, never
    correctness)."""
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(37)
    p1 = rng.integers(0, 96, (9,)).astype(np.int32)
    p2 = rng.integers(0, 96, (6,)).astype(np.int32)
    ref1, ref2 = _paged_refs(gpt, [p1, p2], [6, 5])
    faults.clear()
    try:
        eng = _engine(gpt)
        r1 = eng.add_request(p1, 6)
        assert eng.run()[r1].finish_reason == "length"
        assert eng.stats["cached_pages"] >= 2   # p1's prefix published
        # every allocation for p2 forcibly evicts the LRU cached page
        faults.inject("engine_cache_evict", times=0)
        r2 = eng.add_request(p2, 5)
        done = eng.run()
        np.testing.assert_array_equal(done[r2].sequence, ref2)
        faults.clear()
        st = eng.stats
        assert st["evictions"] >= 2             # drill actually evicted
        hits_before = st["cache_hits"]
        # p1 again: its prefix was evicted -> full re-prefill, bitwise
        r3 = eng.add_request(p1, 6)
        done = eng.run()
        np.testing.assert_array_equal(done[r3].sequence, ref1)
        assert eng.stats["cache_hits"] == hits_before  # true miss
        _assert_pool_conserved(eng)
    finally:
        faults.clear()
