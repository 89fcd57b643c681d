"""Speculative decoding subsystem (ISSUE 9).

Correctness model: greedy engine outputs with ``spec_decode`` on —
either proposer, any drill — must be BITWISE-identical to
``spec_decode`` off and to ``generate(kv_cache='paged')``.  Drafts may
only change how many tokens a dispatch emits, never which; the
acceptance rule guarantees that for ANY proposal, so every test here
pins outputs first and throughput accounting second.

Budget note: the suite reuses the session-scoped ``serving_gpt`` tiny
model and the SAME engine geometry as tests/test_serving_engine.py
(max_slots=2, page_size=4, max_seq_len=32, q_block=2), so the fp
reference programs are already compiled; the speculative tests share
ONE spec program among themselves (spec_k=3 keeps one token budget).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import (ContinuousBatchingEngine,
                                  DraftModelProposer, NGramProposer)
from paddle_tpu.inference.speculative import (accept_greedy,
                                              accept_sampled)
from paddle_tpu.models import generate


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    # session tiny model (tests/conftest.py): compiled programs are
    # shared with test_serving_engine / test_quant_serving
    return serving_gpt


def _ngram_workload(lm):
    """The suite's workload; the LLaMA's first request runs long enough
    to fall into the loop its greedy stream ends in, so that the n-gram
    proposer has drafts the target accepts."""
    if hasattr(lm, "llama"):
        return _workload(3, new=(16, 4, 7, 5))
    return _workload(0)


@pytest.fixture(scope="module")
def draft_gpt():
    """A smaller, differently-seeded GPT: a REAL draft model (its
    greedy picks genuinely differ from the target's)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(1)
    m = GPTForCausalLM(GPTConfig(
        vocab_size=96, hidden_size=16, num_layers=1, num_heads=2,
        max_seq_len=64, dropout=0.0))
    m.eval()
    return m


def _paged_refs(model, prompts, new):
    return [generate(model, p[None, :], max_new_tokens=n,
                     kv_cache="paged").numpy()[0]
            for p, n in zip(prompts, new)]


def _engine(gpt, **kw):
    args = dict(max_slots=2, page_size=4, max_seq_len=32,
                decode_window=4, prefill_chunk=8, q_block=2)
    args.update(kw)
    return ContinuousBatchingEngine(gpt, **args)


def _spec_engine(gpt, **kw):
    args = dict(spec_decode=True, spec_k=3)
    args.update(kw)
    return _engine(gpt, **args)


def _workload(seed=0, lens=(5, 9, 3, 12), new=(6, 4, 7, 5)):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, 96, (n,)).astype(np.int32)
             for n in lens], list(new))


# ----------------------------------------------------------------------
# proposers + acceptance rule, model-free (pure python)
# ----------------------------------------------------------------------

def test_ngram_proposer_prompt_lookup():
    p = NGramProposer(max_ngram=3, min_ngram=1)
    ids = np.array([7, 1, 2, 3, 9, 1, 2, 3], np.int32)
    # tail [1,2,3] occurred earlier at index 1 -> continuation was 9
    np.testing.assert_array_equal(p.propose(0, ids, 2), [9, 1])
    # most RECENT occurrence wins: the tail [5] after two earlier 5s
    ids = np.array([5, 1, 5, 2, 5], np.int32)
    np.testing.assert_array_equal(p.propose(0, ids, 1), [2])
    # no earlier occurrence of any suffix: no drafts
    assert p.propose(0, np.array([1, 2, 3], np.int32), 4).size == 0
    # k caps the continuation
    ids = np.array([4, 8, 8, 8, 4, 8, 8, 8, 4], np.int32)
    assert p.propose(0, ids, 3).size == 3
    assert p.propose(0, ids, 0).size == 0


def test_accept_greedy_rule():
    # m leading matches emit m drafts + the free target token
    emitted, m = accept_greedy([3, 5, 7], [3, 5, 9, 11])
    np.testing.assert_array_equal(emitted, [3, 5, 9])
    assert m == 2
    # full agreement: all K drafts + the bonus token
    emitted, m = accept_greedy([3, 5], [3, 5, 8])
    np.testing.assert_array_equal(emitted, [3, 5, 8])
    assert m == 2
    # first draft wrong: exactly the plain-decode token
    emitted, m = accept_greedy([4], [6, 2])
    np.testing.assert_array_equal(emitted, [6])
    assert m == 0
    # no drafts: a plain 1-token step
    emitted, m = accept_greedy([], [9])
    np.testing.assert_array_equal(emitted, [9])
    assert m == 0


def test_accept_sampled_rejection_rule():
    rng = np.random.default_rng(0)
    v = 8
    lg = np.zeros((3, v), np.float32)
    lg[:, 2] = 50.0          # temperature-scaled target ~ delta at 2
    emitted, m = accept_sampled([2, 2], lg, 1.0, rng)
    np.testing.assert_array_equal(emitted, [2, 2, 2])
    assert m == 2
    # a draft the target gives ~zero mass is rejected and resampled
    # from the residual (never the draft itself)
    emitted, m = accept_sampled([5], lg[:2], 1.0, rng)
    assert m == 0 and emitted.size == 1 and emitted[0] != 5


# ----------------------------------------------------------------------
# engine parity: both proposers, eos, contention
# ----------------------------------------------------------------------

def test_spec_engine_matches_generate_ngram(serving_lm):
    """Slot contention + mid-stream admission with the n-gram proposer:
    every output equals the sequential generate() row AND the spec-off
    engine; drafts were actually proposed and some accepted."""
    prompts, new = _ngram_workload(serving_lm)
    refs = _paged_refs(serving_lm, prompts, new)
    outs = {}
    for spec in (False, True):
        eng = (_spec_engine(serving_lm) if spec
               else _engine(serving_lm))
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        done = eng.run()
        outs[spec] = [done[r].sequence for r in rids]
        if spec:
            st = eng.stats
            assert st["spec_proposed"] > 0
            assert st["spec_accepted"] > 0
            assert 0.0 < st["spec_accept_rate"] <= 1.0
            assert st["pages_in_use"] == 0
    for got_on, got_off, ref in zip(outs[True], outs[False], refs):
        np.testing.assert_array_equal(got_on, ref)
        np.testing.assert_array_equal(got_off, ref)


def test_spec_engine_matches_generate_draft_model(gpt, draft_gpt):
    """The draft-model proposer: a real small LM drafting against its
    own paged pool — outputs bitwise, and the draft pool's free list
    is whole after the drain (page discipline shared with the
    engine)."""
    prompts, new = _workload(3, lens=(5, 9, 3), new=(6, 4, 7))
    refs = _paged_refs(gpt, prompts, new)
    prop = DraftModelProposer(draft_gpt)
    eng = _spec_engine(gpt, spec_proposer=prop)
    assert prop.total_pages == 1 + eng.max_slots * eng.np_per_seq
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    assert eng.stats["spec_proposed"] > 0
    # every request released its draft pages through _release_slot
    assert prop.pages_free == prop.total_pages - 1
    assert not prop._seqs


def test_spec_engine_eos_early_retire(gpt):
    """eos inside an ACCEPTED draft run stops the stream exactly where
    plain decode stops it (host replay of the stop rule mid-accept)."""
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 96, (5,)).astype(np.int32)
    full = generate(gpt, prompt[None, :], max_new_tokens=8).numpy()[0]
    eos = int(full[prompt.size + 1])
    ref = generate(gpt, prompt[None, :], max_new_tokens=8,
                   eos_token_id=eos).numpy()[0]
    eng = _spec_engine(gpt)
    rid = eng.add_request(prompt, 8, eos_token_id=eos)
    done = eng.run()
    got = done[rid].sequence
    assert done[rid].finish_reason == "stop"
    assert got[-1] == eos and got.size < prompt.size + 8
    np.testing.assert_array_equal(got, ref[:got.size])
    assert eng.stats["pages_in_use"] == 0


# ----------------------------------------------------------------------
# fault drills: engine_draft_nan / engine_draft_mismatch (ISSUE 9
# satellite) — victim fails coded, survivors bitwise
# ----------------------------------------------------------------------

def test_spec_engine_draft_nan_drill(gpt):
    """A NaN'd draft (engine_draft_nan poisons the victim's verify
    rows) fails EXACTLY that request with PDT-E018 while the
    co-resident request's stream is bitwise-untouched."""
    from paddle_tpu.core import errors
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(13)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    (ref2,) = _paged_refs(gpt, [p2], [8])
    faults.clear()
    try:
        eng = _spec_engine(gpt)
        r1 = eng.add_request(p1, 8)
        r2 = eng.add_request(p2, 8)
        # the site arms ONLY on verify dispatches (never r1's prefill
        # chunks); at=2 poisons the SECOND verify, so the prefill
        # token and the first verify's tokens survive the failure
        faults.inject("engine_draft_nan", match=str(r1), at=2)
        done = eng.run()
        assert done[r1].finish_reason == "failed"
        assert isinstance(done[r1].error, errors.NonFiniteLogitsError)
        assert done[r1].error.error_code == "PDT-E018"
        assert 0 < done[r1].tokens.size < 8
        assert done[r2].finish_reason == "length"
        np.testing.assert_array_equal(done[r2].sequence, ref2)
        assert eng.stats["failed"] == 1
        assert eng.stats["pages_in_use"] == 0
        # at=1 fires on the FIRST verify — the site never arms on
        # prefill chunks, so the prefill-completion token always
        # survives and the failed verify's tokens are all discarded
        faults.clear()
        eng = _spec_engine(gpt)
        r1 = eng.add_request(p1, 8)
        faults.inject("engine_draft_nan", match=str(r1), at=1)
        done = eng.run()
        assert done[r1].finish_reason == "failed"
        assert done[r1].tokens.size == 1
    finally:
        faults.clear()


def test_spec_engine_draft_mismatch_drill(gpt):
    """engine_draft_mismatch corrupts every proposal: verify rejects
    the drafts, outputs stay BITWISE — the acceptance rule is correct
    for arbitrary garbage drafts.

    The corruption is ``draft + 1 mod vocab``, made before the target
    has spoken, so it can land ON the target's token: on this workload
    the n-gram proposer offers 73 where the target picks 74, and the
    corrupted 74 is accepted — rightly, it is the token plain decode
    emits.  So the drill cannot promise zero accepts.  It promises what
    the site is for: every token emitted is the target's, and the
    accepts fall far below the clean run's (6 of 7 there, 1 of 12
    here) — what is still accepted is a coincidence of that kind."""
    from paddle_tpu.resilience import faults

    prompts, new = _workload(0)
    refs = _paged_refs(gpt, prompts, new)
    faults.clear()
    try:
        clean = _spec_engine(gpt)
        for p, n in zip(prompts, new):
            clean.add_request(p, n)
        clean.run()
        clean_st = clean.stats

        eng = _spec_engine(gpt)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        faults.inject("engine_draft_mismatch", times=0)  # every step
        done = eng.run()
        for rid, ref in zip(rids, refs):
            np.testing.assert_array_equal(done[rid].sequence, ref)
        st = eng.stats
        assert st["spec_proposed"] > 0
        assert st["spec_accepted"] < clean_st["spec_accepted"]
        assert st["spec_accept_rate"] < clean_st["spec_accept_rate"] / 2
    finally:
        faults.clear()


# ----------------------------------------------------------------------
# sampling mode, stats contract, observability, the motif workload
# ----------------------------------------------------------------------

def test_spec_rejection_sampling_deterministic(gpt):
    """spec_temperature > 0 with rejection sampling: runs clean,
    respects stop lengths, and is deterministic under spec_seed (the
    host RNG is the only entropy source)."""
    prompts, new = _workload(3, lens=(5, 9, 3), new=(6, 4, 7))
    outs = []
    for _ in range(2):
        eng = _spec_engine(gpt, spec_temperature=0.8,
                           spec_rejection_sampling=True, spec_seed=7)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        done = eng.run()
        for rid, p, n in zip(rids, prompts, new):
            assert done[rid].finish_reason == "length"
            assert done[rid].tokens.size == n
        outs.append([done[r].sequence for r in rids])
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


def test_spec_stats_appended_backward_compat(gpt):
    """The spec counters APPEND to stats: every pre-existing key keeps
    its exact position (the PR5-PR8 contract), the three spec keys
    follow, then the migration counters PR 20 appended after them, and
    spec_accept_rate is the only non-int besides kv_quant."""
    _OLD_KEYS = [
        "admitted", "retired", "steps", "mixed_steps",
        "decode_dispatches", "tokens_generated", "pages_allocated",
        "peak_pages_in_use", "preemptions", "timeouts", "cancelled",
        "failed", "rejected", "retries", "cache_hits",
        "cache_hit_tokens", "prefill_tokens_requested",
        "prefill_tokens_computed", "cached_pages", "evictions",
        "pages_in_use", "pages_free", "queue_depth", "kv_quant",
        "kv_page_bytes", "kv_bytes_in_use",
    ]
    eng = _engine(gpt)
    st = eng.stats
    assert list(st) == _OLD_KEYS + ["spec_proposed", "spec_accepted",
                                    "spec_accept_rate", "migrated_in",
                                    "migrated_out"]
    assert st["spec_proposed"] == 0 and st["spec_accepted"] == 0
    assert st["spec_accept_rate"] == 0.0
    assert isinstance(st["spec_proposed"], int)
    assert isinstance(st["spec_accept_rate"], float)


def test_spec_timelines_and_metrics(gpt):
    """verify_window events feed the accepted-tokens-per-step
    histogram: count == verify slot-steps, mean >= 1 (every verify
    emits at least the free target token), and the registry carries
    the spec counters."""
    prompts, new = _workload(0)
    eng = _spec_engine(gpt)
    for p, n in zip(prompts, new):
        eng.add_request(p, n)
    eng.run()
    snap = eng.metrics()["serving"]
    h = snap["spec_accepted_per_step"]
    assert h["count"] > 0
    assert h["sum"] == eng.stats["tokens_generated"] - sum(
        1 for _ in prompts)     # prefill emits 1 token/request outside
    assert h["sum"] / h["count"] >= 1.0
    assert snap["spec_proposed"] == eng.stats["spec_proposed"]
    assert snap["spec_accepted"] == eng.stats["spec_accepted"]


def test_spec_ngram_repeated_motif_accepts_over_1p5_a_step(gpt):
    """What speculation buys: on repetitive text (each prompt tiles a
    motif of its own) the model-free n-gram proposer's drafts are
    accepted at more than 1.5 tokens a slot a verify dispatch, with
    outputs equal to the plain engine's and no page leaked."""
    rng = np.random.default_rng(7)
    prompts = [np.tile(rng.integers(0, 96, 4).astype(np.int32), 4)
               for _ in range(4)]
    outs = {}
    for spec in (False, True):
        eng = _spec_engine(gpt) if spec else _engine(gpt)
        rids = [eng.add_request(p, 16) for p in prompts]
        done = eng.run()
        outs[spec] = [done[r].sequence for r in rids]
        assert eng.stats["pages_in_use"] == 0
    for on, off in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(on, off)
    st = eng.stats
    assert st["spec_proposed"] >= st["spec_accepted"] > 0
    assert st["spec_accept_rate"] > 0.5
    h = eng.metrics()["serving"]["spec_accepted_per_step"]
    assert h["sum"] / h["count"] > 1.5
