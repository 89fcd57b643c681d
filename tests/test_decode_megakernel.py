"""Decode megakernel acceptance suite (ISSUE 18).

Correctness model: the fused decode path (``ops/pallas/``
``fused_decode_qkv`` = pre-norm + QKV + RoPE + paged-KV append,
``fused_decode_mlp`` = out-proj + residual + MLP + residual, and the
``fused_decode_epilogue`` = final norm + LM head + guarded argmax) is
gated two ways:

* KERNEL level — every kernel runs under ``interpret=True`` and must be
  BITWISE-identical to its jnp twin across geometries: padded row
  tails, GQA, rotary embeddings, bf16 KV pages, int8-quantized pages.
* ENGINE level — ``megakernel=True`` must produce BITWISE-identical
  token streams to ``megakernel=False`` (and to
  ``generate(kv_cache='paged')``) over the serving workloads that
  stress the scheduler: slot contention, shared-prefix + copy-on-write
  admission, int8 KV quant, speculative decoding, and TP=2.  An
  off-spelling must restore today's compiled decode programs exactly
  (same ``_geometry()`` cache key), and a typo'd spelling raises
  instead of silently picking a path.

The engine tests reuse the session ``serving_gpt`` fixture and the
serving-suite geometry so they ride the already-compiled programs
(tier-1 budget, not semantics).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.core import state as _state
from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate
from paddle_tpu.ops.pallas import fused_decode_mlp as FM
from paddle_tpu.ops.pallas import fused_decode_qkv as FQ

# serving-suite geometry (test_serving_engine.py): same compiled
# programs as the rest of the pinned acceptance block
KW = dict(max_slots=2, page_size=8, max_seq_len=32, decode_window=4,
          prefill_chunk=8, q_block=2)


def _workload(seed=0, lens=(5, 9, 3, 12), new=(6, 4, 7, 5)):
    rng = np.random.default_rng(seed)
    return ([rng.integers(0, 96, (n,)).astype(np.int32)
             for n in lens], list(new))


def _run(model, prompts, new, mk, **kw):
    eng = ContinuousBatchingEngine(model, megakernel=mk, **{**KW, **kw})
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    return [done[r].sequence for r in rids], eng


def _paged_refs(model, prompts, new):
    return [generate(model, p[None, :], max_new_tokens=n,
                     kv_cache="paged").numpy()[0]
            for p, n in zip(prompts, new)]


# ----------------------------------------------------------------------
# kernel vs jnp twin, bitwise (model-free)
# ----------------------------------------------------------------------

def _t(rng, *s):
    return jnp.asarray(rng.normal(size=s), jnp.float32)


def _qkv_case():
    """B=5 deliberately leaves a padded row tail at rows=2 (ceil 5/2=3
    blocks, last half-empty); NP=3 pages x ps=4 slots spans page
    boundaries at every test position."""
    rng = np.random.default_rng(0)
    B, H, nh, hd, NP, ps, P = 5, 32, 4, 8, 3, 4, 12
    pos = jnp.asarray([0, 3, 7, 11, 2], jnp.int32)
    bt = jnp.asarray(rng.integers(0, P, size=(B, NP)), jnp.int32)
    return rng, B, H, nh, hd, NP, ps, P, pos, bt


@pytest.mark.parametrize("rows", [None, 2])
def test_fused_qkv_matches_twin_gpt(rows):
    """Fused QKV (layernorm + packed QKV + paged append) vs its jnp
    twin: bitwise, including the rows=2 padded-tail grid."""
    rng, B, H, nh, hd, NP, ps, P, pos, bt = _qkv_case()
    x, nw, nb = _t(rng, B, H), _t(rng, H), _t(rng, H)
    w = _t(rng, H, 3 * nh * hd) * 0.05
    b = _t(rng, 3 * nh * hd) * 0.1
    kp, vp = _t(rng, nh, P, ps, hd), _t(rng, nh, P, ps, hd)
    kw = dict(norm="layer", eps=1e-5, n_heads=nh, n_kv_heads=nh,
              head_dim=hd, rope_theta=None, rows=rows)
    got = FQ.fused_decode_qkv(x, nw, nb, [w], [b], pos, bt, kp, vp,
                              interpret=True, **kw)
    ref = FQ.fused_decode_qkv_twin(x, nw, nb, [w], [b], pos, bt,
                                   kp, vp, **kw)
    for a, b_ in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


@pytest.mark.parametrize("pages", ["int8", "bf16"])
def test_fused_qkv_matches_twin_llama_gqa(pages):
    """LLaMA shape: rmsnorm, split Q/K/V, GQA (2 KV heads under 4 Q
    heads), rotary at theta=1e4 — against int8-quantized pages (scale
    pools round-trip) and bf16 pages (cast-on-append)."""
    rng, B, H, nh, hd, NP, ps, P, pos, bt = _qkv_case()
    hk = 2
    x, nw = _t(rng, B, H), _t(rng, H)
    wq = _t(rng, H, nh * hd) * 0.05
    wk = _t(rng, H, hk * hd) * 0.05
    wv = _t(rng, H, hk * hd) * 0.05
    kw = dict(norm="rms", eps=1e-6, n_heads=nh, n_kv_heads=hk,
              head_dim=hd, rope_theta=10000.0)
    if pages == "int8":
        kp = jnp.zeros((hk, P, ps, hd), jnp.int8)
        vp = jnp.zeros((hk, P, ps, hd), jnp.int8)
        scales = (jnp.ones((hk, P, ps), jnp.float32),
                  jnp.ones((hk, P, ps), jnp.float32))
    else:
        kp = jnp.zeros((hk, P, ps, hd), jnp.bfloat16)
        vp = jnp.zeros((hk, P, ps, hd), jnp.bfloat16)
        scales = (None, None)
    got = FQ.fused_decode_qkv(x, nw, None, [wq, wk, wv], [], pos, bt,
                              kp, vp, *scales, interpret=True, **kw)
    ref = FQ.fused_decode_qkv_twin(x, nw, None, [wq, wk, wv], [], pos,
                                   bt, kp, vp, *scales, **kw)
    for a, b_ in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_fused_mlp_matches_twin():
    """Fused out-proj+residual+MLP+residual vs twin: GPT (gelu,
    biases), LLaMA (swiglu, rows=2 padded tail), and the TP partial
    form (stops before the down-proj psum)."""
    rng = np.random.default_rng(1)
    B, H, nh, hd = 5, 32, 4, 8
    I = 4 * H
    x, nw, nb = _t(rng, B, H), _t(rng, H), _t(rng, H)
    att = _t(rng, B, nh * hd)
    wo, bo = _t(rng, nh * hd, H) * 0.05, _t(rng, H) * 0.1
    w1, b1 = _t(rng, H, I) * 0.05, _t(rng, I) * 0.1
    w2, b2 = _t(rng, I, H) * 0.05, _t(rng, H) * 0.1
    g = FM.fused_decode_mlp(x, att, wo, bo, nw, nb, w1, b1, w2, b2,
                            arch="gpt", norm="layer", eps=1e-5,
                            interpret=True)
    r = FM.fused_decode_mlp_twin(x, att, wo, bo, nw, nb, w1, b1, w2,
                                 b2, arch="gpt", norm="layer", eps=1e-5)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    wu = _t(rng, H, I) * 0.05
    g = FM.fused_decode_mlp(x, att, wo, None, nw, None, w1, None, w2,
                            None, w_up=wu, arch="llama", norm="rms",
                            eps=1e-6, rows=2, interpret=True)
    r = FM.fused_decode_mlp_twin(x, att, wo, None, nw, None, w1, None,
                                 w2, None, w_up=wu, arch="llama",
                                 norm="rms", eps=1e-6, rows=2)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    g = FM.fused_decode_mlp_partial(x, nw, nb, w1, b1, w2, arch="gpt",
                                    norm="layer", eps=1e-5,
                                    interpret=True)
    r = FM.fused_decode_mlp_partial_twin(x, nw, nb, w1, b1, w2,
                                         arch="gpt", norm="layer",
                                         eps=1e-5)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_fused_epilogue_matches_twin_and_poison_drill():
    """Sampling epilogue (final norm + LM head + guarded argmax) vs
    twin, bitwise — and the guard drill: a NaN-poisoned row must raise
    its ``bad`` flag and emit token 0 (the engine's quarantine
    sentinel), with clean rows untouched."""
    rng = np.random.default_rng(2)
    B, H, V = 5, 32, 17
    x, nw, nb = _t(rng, B, H), _t(rng, H), _t(rng, H)
    wlm = _t(rng, V, H) * 0.05
    poison = jnp.asarray([0.0, 0.0, float("nan"), 0.0, 0.0],
                         jnp.float32)
    got = FM.fused_decode_epilogue(x, nw, nb, wlm, None, poison,
                                   norm="layer", eps=1e-5,
                                   transpose_lm=True, interpret=True)
    ref = FM.fused_decode_epilogue_twin(x, nw, nb, wlm, None, poison,
                                        norm="layer", eps=1e-5,
                                        transpose_lm=True)
    for a, b_ in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))
    logits, nxt, bad = got
    assert bool(bad[2]) and int(nxt[2]) == 0          # poisoned row
    assert not bool(bad[0]) and not bool(bad[4])      # clean rows
    # logits are returned PRE-poison (observability keeps real values)
    assert np.isfinite(np.asarray(logits)).all()


# ----------------------------------------------------------------------
# engine: megakernel on/off bitwise over the serving workloads
# ----------------------------------------------------------------------

def test_engine_megakernel_slot_contention_bitwise(serving_gpt):
    """4 ragged requests through 2 slots with mid-stream admission:
    megakernel on == off == sequential generate(), bitwise, and the
    scheduler behaved identically both ways."""
    prompts, new = _workload()
    refs = _paged_refs(serving_gpt, prompts, new)
    # An engine's FIRST decode dispatch on a geometry is a single
    # scalar step that compiles the step program; an engine that finds
    # the program in the cache on the (session-shared) model goes
    # straight to windows.  Whichever suite ran earlier in this worker
    # may have left the unfused program there and not the fused one, so
    # the two counts below differed by that one bootstrap dispatch
    # under --dist loadfile while agreeing when this file ran alone.
    # Put both programs in the cache before counting.
    for mk in (False, True):
        _run(serving_gpt, prompts[:1], new[:1], mk)
    off, e_off = _run(serving_gpt, prompts, new, False)
    on, e_on = _run(serving_gpt, prompts, new, True)
    for a, b, r in zip(off, on, refs):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, r)
    assert e_on.stats["mixed_steps"] >= 2             # contention happened
    assert e_on.stats["decode_dispatches"] == e_off.stats[
        "decode_dispatches"]                          # same window schedule


def test_engine_megakernel_shared_prefix_cow_bitwise(serving_gpt):
    """Shared-prefix admissions under prefix_cache: later requests map
    published pages (cache hits > 0) and the COW re-admission of an
    identical prompt recomputes one token — bitwise on/off throughout."""
    rng = np.random.default_rng(29)
    shared = rng.integers(0, 96, (16,)).astype(np.int32)  # 2 full pages
    tails = [rng.integers(0, 96, (n,)).astype(np.int32)
             for n in (3, 2, 5, 1)]
    prompts = [np.concatenate([shared, t]) for t in tails]
    new = [6, 5, 4, 6]
    outs = {}
    for mk in (False, True):
        eng = ContinuousBatchingEngine(serving_gpt, megakernel=mk,
                                       prefix_cache=True, **KW)
        rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
        done = eng.run()
        assert eng.stats["cache_hits"] >= 2           # prefix reuse ran
        # COW drill: the page-aligned shared prompt (2 full pages) is
        # fully cached by now, so its admission takes the copy-on-write
        # path — exactly ONE token recomputed for the last position
        base = eng.stats["prefill_tokens_computed"]
        r2 = eng.add_request(shared, 4)
        done2 = eng.run()
        assert eng.stats["prefill_tokens_computed"] - base == 1
        outs[mk] = ([done[r].sequence for r in rids]
                    + [done2[r2].sequence])
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(a, b)


def test_engine_megakernel_kv_quant_bitwise(serving_gpt):
    """int8 KV quant: the fused QKV kernel quantizes-and-appends inside
    the megakernel; token streams stay bitwise vs the unfused quant
    path."""
    prompts, new = _workload(seed=3)
    off, _ = _run(serving_gpt, prompts, new, False, kv_quant=True)
    on, _ = _run(serving_gpt, prompts, new, True, kv_quant=True)
    for a, b in zip(off, on):
        np.testing.assert_array_equal(a, b)


def test_engine_megakernel_spec_decode_bitwise(serving_gpt):
    """Speculative decoding composes: verify segments run through the
    mixed program regardless of the flag, so megakernel on/off (and
    spec on/off) all agree bitwise."""
    prompts, new = _workload(seed=4)
    plain, _ = _run(serving_gpt, prompts, new, False)
    off, _ = _run(serving_gpt, prompts, new, False,
                  spec_decode=True, spec_k=3)
    on, _ = _run(serving_gpt, prompts, new, True,
                 spec_decode=True, spec_k=3)
    for a, b, c in zip(plain, off, on):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_engine_megakernel_tp2_bitwise(serving_gpt):
    """TP=2: the fused TP decode body keeps the unfused psum schedule
    (one per out-proj, one per MLP down), so megakernel on == off ==
    the single-device stream, bitwise — fp and kv_quant."""
    mesh2 = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    prompts, new = _workload(seed=5)
    single, _ = _run(serving_gpt, prompts, new, False)
    tp_off, _ = _run(serving_gpt, prompts, new, False, mesh=mesh2)
    tp_on, _ = _run(serving_gpt, prompts, new, True, mesh=mesh2)
    for a, b, c in zip(single, tp_off, tp_on):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)
    tq_off, _ = _run(serving_gpt, prompts, new, False, mesh=mesh2,
                     kv_quant=True)
    tq_on, _ = _run(serving_gpt, prompts, new, True, mesh=mesh2,
                    kv_quant=True)
    for a, b in zip(tq_off, tq_on):
        np.testing.assert_array_equal(a, b)


# ----------------------------------------------------------------------
# flag plumbing: spellings, restore, strictness
# ----------------------------------------------------------------------

def test_megakernel_off_spelling_restores_default_programs(serving_gpt):
    """An explicit off-spelling must be INDISTINGUISHABLE from the
    default: same parsed value and the same ``_geometry()`` program
    cache key, so no decode program recompiles when the flag is
    toggled back off."""
    base = ContinuousBatchingEngine(serving_gpt, **KW)
    for spelling in ("off", "false", "0", "no", False):
        eng = ContinuousBatchingEngine(serving_gpt,
                                       megakernel=spelling, **KW)
        assert eng.megakernel is False
        assert eng._geometry() == base._geometry()
    for spelling in ("on", "true", "1", "yes", True):
        eng = ContinuousBatchingEngine(serving_gpt,
                                       megakernel=spelling, **KW)
        assert eng.megakernel is True
        assert eng._geometry() != base._geometry()


def test_megakernel_flag_and_strict_spelling(serving_gpt):
    """The ``serving_megakernel`` flag sets the default (kwarg still
    wins), and a typo'd spelling raises instead of silently running
    the wrong decode program."""
    old = _state.get_flag("serving_megakernel")
    try:
        _state.set_flags({"serving_megakernel": True})
        assert ContinuousBatchingEngine(
            serving_gpt, **KW).megakernel is True
        assert ContinuousBatchingEngine(
            serving_gpt, megakernel="off", **KW).megakernel is False
    finally:
        _state.set_flags({"serving_megakernel": old})
    with pytest.raises(ValueError, match="megakernel"):
        ContinuousBatchingEngine(serving_gpt, megakernel="fast", **KW)
