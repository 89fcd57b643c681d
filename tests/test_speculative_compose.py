"""Speculative decoding (ISSUE 9) composed with the prefix cache, int8 KV
pages and preemption.  The other part of
tests/test_speculative.py, whose correctness model and engine geometry
this keeps: a file of its own where it shares no fixture but the
session's models, so that neither is over 200 s of tier-1.
"""
import numpy as np
import pytest

from test_speculative import _engine, _paged_refs, _spec_engine, _workload


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    return serving_gpt     # session tiny model (tests/conftest.py)


# ----------------------------------------------------------------------
# composition: prefix cache, kv_quant, preemption
# ----------------------------------------------------------------------

def test_spec_engine_prefix_cache_compose(gpt):
    """Shared-prefix traffic with spec on: published pages hold only
    ACCEPTED tokens (rejected drafts are rolled back positionally), so
    later admissions hit the cache and stay bitwise; pool conservation
    holds throughout."""
    rng = np.random.default_rng(29)
    shared = rng.integers(0, 96, (12,)).astype(np.int32)
    tails = [rng.integers(0, 96, (n,)).astype(np.int32)
             for n in (3, 2, 5, 1)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    new = [6, 5, 4, 6]
    refs = _paged_refs(gpt, prompts, new)
    eng = _spec_engine(gpt)           # prefix cache defaults ON
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = eng.stats
    assert st["cache_hits"] >= 2
    assert st["prefill_tokens_computed"] < st["prefill_tokens_requested"]
    assert st["spec_accepted"] > 0    # speculation ran alongside
    eng._cache.check()                # PDT-E019 conservation audit
    assert (st["pages_in_use"] + st["pages_free"]
            + st["cached_pages"]) == eng.total_pages - 1
    assert st["pages_in_use"] == 0


def test_spec_engine_kv_quant_token_identical(serving_lm):
    """int8 KV + speculation: quantized writes for accepted positions
    are byte-identical to the non-speculative quant path, so the spec
    quant engine's streams equal the plain quant engine's exactly."""
    prompts, new = _workload(3, lens=(5, 9, 3), new=(6, 4, 7))
    outs = {}
    for spec in (False, True):
        eng = (_spec_engine(serving_lm, kv_quant=True) if spec
               else _engine(serving_lm, kv_quant=True))
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        done = eng.run()
        outs[spec] = [done[r].sequence for r in rids]
        assert eng.stats["kv_quant"] is True
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)


def test_spec_engine_forced_preemption_bitwise(gpt):
    """The engine_page_pressure drill under spec_decode: the victim
    requeues, re-prefills (proposer state dropped with its pages) and
    both outputs stay bitwise."""
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(5)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    ref1, ref2 = _paged_refs(gpt, [p1, p2], [8, 8])
    faults.clear()
    try:
        eng = _spec_engine(gpt)
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
