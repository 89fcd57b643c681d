"""Quantized serving path (ISSUE 7) under grouped-query attention: the
int8-KV engine on the LLaMA of the serving suites, and the two page
writers below the engine.  The cases of tests/test_quant_serving.py that
share none of its fixtures, in a file of their own so that neither is
over 200 s of tier-1; its correctness model holds here.
"""
import numpy as np
import pytest

from paddle_tpu.inference import ContinuousBatchingEngine
from paddle_tpu.models import generate


def test_quant_engine_tokens_match_fp_llama_gqa(serving_llama_gqa):
    m = serving_llama_gqa
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, (n,)).astype(np.int32)
               for n in (7, 4, 11)]
    new = [5, 6, 4]
    refs = [generate(m, p[None, :], max_new_tokens=n).numpy()[0]
            for p, n in zip(prompts, new)]
    eng = ContinuousBatchingEngine(m, max_slots=2, page_size=8,
                                   max_seq_len=32, decode_window=3,
                                   prefill_chunk=6, q_block=2,
                                   pages_per_block=1, kv_quant=True)
    rids = [eng.add_request(p, n) for p, n in zip(prompts, new)]
    done = eng.run()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid].sequence, ref)


# ----------------------------------------------------------------------
# the two page writers, below the engine
# ----------------------------------------------------------------------

@pytest.mark.parametrize("pages", ["int8", "bf16"])
def test_slot_and_ragged_appends_fill_identical_pages_gqa(pages):
    """What prefix reuse rests on, held at the pools: rotary GQA keys
    appended one token a step (``paged_slot_attention``, the decode
    path) and as one chunk per slot (``ragged_paged_step``, the mixed
    path) leave the same bytes in int8 pages with their scale pools
    and in bf16 pages, and the last token attends to them as a dense
    softmax over the stored values does."""
    import jax.numpy as jnp

    from paddle_tpu import ops
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.generation import (paged_slot_attention,
                                              ragged_paged_step, rope_at)
    from paddle_tpu.quantization import kv_dequantize

    rng = np.random.default_rng(7)
    B, hq, hk, d, ps, NP, qb = 3, 4, 2, 8, 4, 3, 2
    lens = [5, 9, 2]                      # crosses pages; ragged tails
    P, T = 1 + B * NP, max(lens)
    ids = np.arange(1, P)
    rng.shuffle(ids)
    bt = Tensor(jnp.asarray(ids.reshape(B, NP).astype(np.int32)))
    q, k, v = (rng.normal(size=(B, T, h, d)).astype(np.float32)
               for h in (hq, hk, hk))

    def t(a):
        return Tensor(jnp.asarray(a))

    def pools():
        dt = jnp.int8 if pages == "int8" else jnp.bfloat16
        data = [Tensor(jnp.zeros((hk, P, ps, d), dt)) for _ in "kv"]
        if pages == "int8":
            return data + [Tensor(jnp.ones((hk, P, ps), jnp.float32))
                           for _ in "kv"]
        return data + [None, None]

    # one token a step; a finished slot rewrites its last token
    kp, vp, ks, vs = pools()
    last = {}
    for step in range(T):
        pos = np.array([min(step, n - 1) for n in lens], np.int32)
        row = np.arange(B)
        out, kp, vp, *sc = paged_slot_attention(
            rope_at(t(q[row, pos][:, None]), t(pos)),
            rope_at(t(k[row, pos][:, None]), t(pos)),
            t(v[row, pos][:, None]), kp, vp, t(pos), bt,
            k_scales=ks, v_scales=vs)
        ks, vs = sc or (None, None)
        for b, n in enumerate(lens):
            if step == n - 1:
                last[b] = (np.asarray(out._read())[b, 0],
                           np.asarray(rope_at(t(q[b:b + 1, n - 1:n]),
                                              t(pos[b:b + 1]))._read())[0, 0])
    stepwise = [x for x in (kp, vp, ks, vs) if x is not None]

    # the same tokens, one chunk per slot at a q_block edge
    segs = [-(-n // qb) * qb for n in lens]
    starts = np.cumsum([0] + segs[:-1])
    tot = sum(segs)
    tq, tk, tv = (np.zeros((tot, h, d), np.float32) for h in (hq, hk, hk))
    tpos, tslot, tvalid = (np.zeros(tot, np.int32) for _ in range(3))
    for b, n in enumerate(lens):
        at = slice(starts[b], starts[b] + n)
        tq[at], tk[at], tv[at] = q[b, :n], k[b, :n], v[b, :n]
        tpos[at], tslot[at], tvalid[at] = np.arange(n), b, 1
    kp, vp, ks, vs = pools()
    out, *chunked = ragged_paged_step(
        ops.reshape(rope_at(t(tq[None]), t(tpos)), [tot, hq, d]),
        ops.reshape(rope_at(t(tk[None]), t(tpos)), [tot, hk, d]),
        t(tv), kp, vp, t(tpos), t(tslot), t(tvalid),
        t(np.asarray(lens, np.int32)), t(np.asarray(lens, np.int32)), bt,
        q_block=qb, k_scales=ks, v_scales=vs)
    assert len(chunked) == len(stepwise) == (4 if pages == "int8" else 2)
    for a, c in zip(stepwise, chunked):
        # page 0 is the null page: the chunk's padding rows land there
        np.testing.assert_array_equal(np.asarray(a._read())[:, 1:],
                                      np.asarray(c._read())[:, 1:])

    kd, vd = (np.asarray(x._read()).astype(np.float32)
              for x in stepwise[:2])
    if pages == "int8":
        kd, vd = (np.asarray(kv_dequantize(stepwise[i]._read(),
                                           stepwise[i + 2]._read()))
                  for i in (0, 1))
    table = np.asarray(bt._read())
    out = np.asarray(out._read())
    for b, n in enumerate(lens):
        got, qr = last[b]
        np.testing.assert_allclose(out[starts[b] + n - 1], got,
                                   rtol=1e-5, atol=1e-5)
        pg, sl = table[b, np.arange(n) // ps], np.arange(n) % ps
        kk = np.repeat(kd[:, pg, sl], hq // hk, axis=0)     # [hq, n, d]
        vv = np.repeat(vd[:, pg, sl], hq // hk, axis=0)
        w = np.einsum("hd,hnd->hn", qr, kk) / np.sqrt(d)
        w = np.exp(w - w.max(-1, keepdims=True))
        ref = np.einsum("hn,hnd->hd", w / w.sum(-1, keepdims=True), vv)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
