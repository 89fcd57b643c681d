"""Quantized serving path (ISSUE 7).

Correctness model, layered:

* the int8 ragged attention kernel and the fused weight-only matmul are
  bitwise against their jnp twins in interpret mode (kernel-level tests
  in ``tests/test_pallas.py`` / ``tests/test_quantization.py``);
* the QUANT ENGINE's greedy token streams are IDENTICAL to the fp
  engine / ``generate()`` on the tiny-model serving workloads (int8
  absmax per-vector error does not flip tiny-model argmax — asserted,
  not assumed);
* the prefix-cache drills (COW, eviction, preempt-requeue restore) and
  the pool-conservation audit re-run unchanged with
  ``serving_kv_quant=on`` — scale side-pools ride the same block
  tables, so the scheduling layer never special-cases them;
* with the flag off the engine is the fp path bitwise (same pools, same
  programs, same bytes — pinned against ``generate(kv_cache='paged')``).

The workloads deliberately REPLAY test_serving_engine.py's fp drills
(same rng seeds, prompts, geometries) on the session-shared tiny model:
the fp reference programs are already compiled, so the quant suite pays
only for its own quant-geometry programs.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate


@pytest.fixture(scope="module")
def gpt(serving_gpt):
    return serving_gpt     # session tiny model (tests/conftest.py)


def _refs(model, prompts, new, kv="dense"):
    return [generate(model, p[None, :], max_new_tokens=n,
                     kv_cache=kv).numpy()[0]
            for p, n in zip(prompts, new)]


def _engine(model, **kw):
    args = dict(max_slots=2, page_size=4, max_seq_len=32,
                decode_window=4, prefill_chunk=8, q_block=2)
    args.update(kw)
    return ContinuousBatchingEngine(model, **args)


def _assert_conserved(eng):
    st = eng.stats
    assert st["pages_in_use"] == 0
    assert (st["pages_free"] + st["cached_pages"]
            == eng.total_pages - 1)
    eng._cache.check()


# ----------------------------------------------------------------------
# token parity + byte accounting
# ----------------------------------------------------------------------

def test_quant_engine_tokens_match_fp_gpt(gpt):
    """The slot-contention workload through the int8-KV engine: every
    greedy stream equals the fp generate() reference token for token,
    and the mixed (chunked prefill) + windowed decode paths both ran."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (5, 9, 3, 12)]
    new = [6, 4, 7, 5]
    refs = _refs(gpt, prompts, new)
    eng = _engine(gpt, kv_quant=True)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    assert eng.stats["kv_quant"] is True
    assert eng.stats["mixed_steps"] >= 2
    assert eng.stats["decode_dispatches"] >= 1
    _assert_conserved(eng)
    # int8 data pools + f32 scale side-pools actually installed
    cfg = gpt.cfg
    assert len(eng._caches) == 4 * cfg.num_layers
    assert str(eng._caches[0].dtype).endswith("int8")
    assert str(eng._caches[2 * cfg.num_layers].dtype).endswith("float32")


def test_quant_kv_bytes_per_sequence_halved(gpt):
    """The acceptance gate: KV pool bytes per resident sequence drop
    below HALF of fp32 — pages hold the same token counts, so byte
    accounting per page is the per-sequence claim.  Exact layout:
    D*1 (int8) + 4 (f32 scale) per (head, slot) vs D*4 fp32.
    Construction-only (no dispatch): the gauges are static geometry."""
    cfg = gpt.cfg
    fp = _engine(gpt).stats
    q = _engine(gpt, kv_quant=True).stats
    assert q["kv_page_bytes"] * 2 <= fp["kv_page_bytes"]
    d = cfg.head_dim
    assert q["kv_page_bytes"] == fp["kv_page_bytes"] * (d + 4) // (4 * d)
    assert q["kv_bytes_in_use"] == 0 and fp["kv_bytes_in_use"] == 0


def test_quant_flag_off_restores_fp_engine_bitwise(gpt):
    """``serving_kv_quant`` off (the default): fp32 pools, 2L cache
    list, outputs bitwise-equal to generate(kv_cache='paged') — the
    refactored code path with quant disabled IS the old fp path.  (The
    whole fp serving suite, test_serving_engine.py, runs flag-off too;
    this pins the flag/kwarg plumbing itself.)"""
    from paddle_tpu.core import state

    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (6, 8, 5, 7)]
    new = [8, 7, 8, 6]
    refs = _refs(gpt, prompts, new, kv="paged")
    assert state.get_flag("serving_kv_quant") is False  # default off
    eng = _engine(gpt)                      # flag-driven: fp
    assert eng.kv_quant is False
    assert len(eng._caches) == 2 * gpt.cfg.num_layers
    assert str(eng._caches[0].dtype).endswith("float32")
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    # flag flips the default; kwarg spellings parse like prefix_cache's
    state.set_flags({"serving_kv_quant": True})
    try:
        assert _engine(gpt).kv_quant is True
        assert _engine(gpt, kv_quant="off").kv_quant is False
    finally:
        state.set_flags({"serving_kv_quant": False})
    assert _engine(gpt, kv_quant="on").kv_quant is True
    # strict parse: lossy quantization must never engage on a typo
    with pytest.raises(ValueError, match="kv_quant"):
        _engine(gpt, kv_quant="disabled")


# ----------------------------------------------------------------------
# prefix-cache drills under quant
# ----------------------------------------------------------------------

def test_quant_prefix_cache_shared_and_cow(gpt):
    """Shared-prefix reuse AND the copy-on-write full-hit path with
    int8 pages: scale side-pools travel with the matched/copied pages
    (same block tables, same COW dispatch), so hits stay
    token-identical and exactly one token recomputes on a full hit."""
    rng = np.random.default_rng(29)
    shared = rng.integers(0, 96, (12,)).astype(np.int32)  # 3 full pages
    tails = [rng.integers(0, 96, (n,)).astype(np.int32)
             for n in (3, 2, 5, 1)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    new = [6, 5, 4, 6]
    refs = _refs(gpt, prompts, new, kv="paged")
    eng = _engine(gpt, kv_quant=True)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)
    st = eng.stats
    assert st["cache_hits"] >= 2   # later admissions rode shared pages
    assert st["prefill_tokens_computed"] < st["prefill_tokens_requested"]
    _assert_conserved(eng)

    # COW: full page-aligned hit recomputes exactly one token
    prompt = rng.integers(0, 96, (8,)).astype(np.int32)   # 2 full pages
    (ref,) = _refs(gpt, [prompt], [6], kv="paged")
    eng = _engine(gpt, kv_quant=True)
    r1 = eng.add_request(prompt, 6)
    np.testing.assert_array_equal(eng.run()[r1].sequence, ref)
    base = eng.stats["prefill_tokens_computed"]
    r2 = eng.add_request(prompt, 6)
    np.testing.assert_array_equal(eng.run()[r2].sequence, ref)
    assert eng.stats["prefill_tokens_computed"] - base == 1
    _assert_conserved(eng)


def test_quant_preempt_requeue_and_evict_drills(gpt):
    """The forced-preemption and forced-eviction drills with int8
    pages: victims republish and restore, evicted prefixes re-prefill,
    every stream token-identical to the fp reference.  (The drills
    replay test_engine_preempt_requeue_recompute_drop /
    test_engine_cache_evict_drill_bitwise on the shared engine
    geometry, so only the quant programs compile fresh; the truly
    starved-pool preemption path is the same allocator code, drilled fp
    in test_engine_preempt_requeue_bitwise.)"""
    from paddle_tpu.resilience import faults

    rng = np.random.default_rng(41)
    p1 = rng.integers(0, 96, (6,)).astype(np.int32)
    p2 = rng.integers(0, 96, (7,)).astype(np.int32)
    refs = _refs(gpt, [p1, p2], [8, 8], kv="paged")
    faults.clear()
    try:
        eng = _engine(gpt, kv_quant=True)
        r1 = eng.add_request(p1, 8)
        r2 = eng.add_request(p2, 8)
        # r1's growth hits injected pressure -> r2 (latest) preempts
        faults.inject("engine_page_pressure", match=str(r1))
        done = eng.run()
        np.testing.assert_array_equal(done[r1].sequence, refs[0])
        np.testing.assert_array_equal(done[r2].sequence, refs[1])
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["cache_hits"] >= 1   # victim restored from its
        _assert_conserved(eng)                # own published int8 pages
    finally:
        faults.clear()

    # forced eviction: cached int8 prefix pages reclaimed, re-admission
    # of the evicted prefix re-prefills bitwise
    rng = np.random.default_rng(37)
    p1 = rng.integers(0, 96, (9,)).astype(np.int32)
    (ref1,) = _refs(gpt, [p1], [6], kv="paged")
    faults.clear()
    try:
        eng = _engine(gpt, kv_quant=True)
        r1 = eng.add_request(p1, 6)
        np.testing.assert_array_equal(eng.run()[r1].sequence, ref1)
        assert eng.stats["cached_pages"] >= 2
        faults.inject("engine_cache_evict", times=0)
        r2 = eng.add_request(p1, 6)
        done = eng.run()
        faults.clear()
        np.testing.assert_array_equal(done[r2].sequence, ref1)
        assert eng.stats["evictions"] >= 1
        _assert_conserved(eng)
    finally:
        faults.clear()


# ----------------------------------------------------------------------
# weight-only generation path
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_q(gpt):
    """Weight-only int8 twin of the session tiny model (same seed +
    config rebuilds identical fp weights before the swap)."""
    from paddle_tpu.quantization import weight_only_quantize

    paddle.seed(0)
    mq = weight_only_quantize(type(gpt)(gpt.cfg))
    mq.eval()
    return mq


def test_weight_only_model_generate(gpt, gpt_q):
    """``weight_only_quantize`` swaps every Linear for the fused int8
    path; generate() serves the swapped model with token streams equal
    to the fp model's (tiny-model argmax is int8-weight stable —
    asserted).  Dense and paged decode both route every projection
    through the fused kernel's jnp twin on CPU."""
    from paddle_tpu.quantization import WeightOnlyLinear

    assert isinstance(gpt_q.gpt.blocks[0].attn.qkv, WeightOnlyLinear)
    assert str(gpt_q.gpt.blocks[0].attn.qkv.qweight.dtype
               ).endswith("int8")
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 96, (7,)).astype(np.int32)
               for _ in range(2)]
    refs = _refs(gpt, prompts, [6, 6], kv="paged")
    for p, ref in zip(prompts, refs):
        out = generate(gpt_q, p[None, :], max_new_tokens=6,
                       kv_cache="paged").numpy()[0]
        np.testing.assert_array_equal(out, ref)


def test_weight_only_bytes_under_half(gpt, gpt_q):
    """What weight-only quantization buys at batch 1 is the weight
    term of the decode roofline: the bytes the swapped model HOLDS
    (int8 weights, float32 per-channel scales, everything else at
    float width) are under half the float model's."""
    from paddle_tpu.quantization import WeightOnlyLinear

    def nbytes(t):
        return int(np.prod(t.shape)) * np.dtype(
            str(t.dtype).split(".")[-1]).itemsize

    fp = sum(nbytes(p) for p in gpt.parameters())
    swapped = [l for l in gpt_q.sublayers(include_self=True)
               if isinstance(l, WeightOnlyLinear)]
    assert len(swapped) >= 4 * gpt.cfg.num_layers
    q = (sum(nbytes(p) for p in gpt_q.parameters())
         + sum(nbytes(l.qweight) + nbytes(l.scale) for l in swapped))
    assert q < 0.5 * fp, (q, fp)
