"""KV-cache generation (greedy/temperature/nucleus) for the LM families.
Parity model: cached decoding must reproduce the no-cache full-forward
argmax sequence exactly."""
import numpy as np
import pytest

import _traced
import paddle_tpu as paddle
from paddle_tpu.models import generate
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def _greedy_nocache(model, ids, steps):
    """Reference decoding: full forward each step, argmax last logits.
    Each step is a length of its own, so op by op every op compiles
    again at every step: one program a length instead."""
    out = ids.copy()
    for _ in range(steps):
        logits = np.asarray(_traced.forward(model, out))
        nxt = logits[:, -1].argmax(-1).astype(out.dtype)
        out = np.concatenate([out, nxt[:, None]], axis=1)
    return out


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_cached_greedy_matches_full_forward(family):
    paddle.seed(0)
    if family == "gpt":
        model = GPTForCausalLM(GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=32, dropout=0.0))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=32))
    model.eval()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 96, (2, 5)).astype(np.int32)

    got = generate(model, prompt, max_new_tokens=6).numpy()
    ref = _greedy_nocache(model, prompt, 6)
    np.testing.assert_array_equal(got, ref)


def test_generate_compiles_once():
    """The decode step must not retrace per token, and repeat calls with
    the same shapes must reuse the compiled program."""
    paddle.seed(1)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=32, dropout=0.0))
    model.eval()
    prompt = np.zeros((1, 3), np.int32)
    out = generate(model, prompt, max_new_tokens=8)
    assert tuple(out.shape) == (1, 11)
    step_fn = model._decode_step_cache[(1, 11, "dense", 0)]
    assert len(step_fn._cache) == 1  # one signature, one program
    exe = next(iter(step_fn._cache.values()))
    n = getattr(exe, "trace_count", 1)
    generate(model, prompt, max_new_tokens=8)  # second call: no retrace
    assert len(step_fn._cache) == 1
    assert getattr(exe, "trace_count", 1) == n


def test_top_p_and_eos():
    paddle.seed(2)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=32, dropout=0.0))
    model.eval()
    prompt = np.ones((2, 3), np.int32)
    out = generate(model, prompt, max_new_tokens=5, top_p=0.9,
                   seed=7).numpy()
    out2 = generate(model, prompt, max_new_tokens=5, top_p=0.9,
                    seed=7).numpy()
    np.testing.assert_array_equal(out, out2)  # seeded -> reproducible
    # eos stops early and pads with eos
    with paddle.no_grad():
        logits = model(paddle.to_tensor(prompt)).numpy()
    eos = int(logits[0, -1].argmax())  # first generated token = eos
    out3 = generate(model, prompt[:1], max_new_tokens=5,
                    eos_token_id=eos).numpy()
    assert out3.shape[1] <= 3 + 5
    assert out3[0, 3] == eos


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_paged_kv_cache_matches_dense(family):
    """Paged decode (page pool + block tables + Pallas paged kernel) must
    reproduce the dense-cache greedy sequence exactly."""
    paddle.seed(0)
    if family == "gpt":
        model = GPTForCausalLM(GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=64))
    model.eval()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 96, (2, 5)).astype(np.int32)

    dense = generate(model, prompt, max_new_tokens=9).numpy()
    # page_size=8 with max_len 14 -> 2 pages/seq, second partially filled
    paged = generate(model, prompt, max_new_tokens=9,
                     kv_cache="paged", page_size=8).numpy()
    np.testing.assert_array_equal(paged, dense)


@pytest.mark.parametrize("family,cache", [("gpt", "dense"),
                                          ("gpt", "paged"),
                                          ("llama", "dense"),
                                          ("llama", "paged")])
def test_batched_prefill_matches_token_by_token(family, cache):
    """One compiled whole-prompt prefill pass must reproduce the pure
    token-by-token sequence exactly, for both cache kinds.

    Numerics: on the CPU suite both paths run f32 XLA attention; the
    llama rope differs between f64-table (prefill, same as the training
    path) and traced-f32 (decode) angles — the identical low-order
    tolerance the long-standing cached-vs-full parity test relies on,
    so exact argmax equality holds at these scales."""
    paddle.seed(0)
    if family == "gpt":
        model = GPTForCausalLM(GPTConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            max_seq_len=64, dropout=0.0))
    else:
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=96, hidden_size=32, num_layers=2, num_heads=4,
            num_kv_heads=2, max_seq_len=64))
    model.eval()
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, 96, (2, 13)).astype(np.int32)  # odd length:
    # paged pages (size 8) end mid-page after the prompt
    kw = dict(kv_cache=cache, page_size=8) if cache == "paged" else {}
    with_pf = generate(model, prompt, max_new_tokens=7, prefill=True,
                       **kw).numpy()
    without = generate(model, prompt, max_new_tokens=7, prefill=False,
                       **kw).numpy()
    np.testing.assert_array_equal(with_pf, without)


def test_decode_window_matches_scalar_dense_and_paged():
    """K-step scanned decode (one dispatch per K tokens, on-device
    sampling) must produce exactly the per-token greedy tokens, for both
    cache kinds (VERDICT r3 item 9)."""
    from paddle_tpu.models.generation import generate

    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 7)).astype(np.int32)
    ref = generate(m, paddle.to_tensor(ids), max_new_tokens=21,
                   decode_window=1).numpy()
    for kv in ("dense", "paged"):
        win = generate(m, paddle.to_tensor(ids), max_new_tokens=21,
                       kv_cache=kv, decode_window=8).numpy()
        np.testing.assert_array_equal(win, ref)


def test_decode_window_eos_and_tail():
    from paddle_tpu.models.generation import generate

    cfg = GPTConfig(vocab_size=97, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=96, dropout=0.0)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    ids = np.random.default_rng(0).integers(0, 97, (2, 7)).astype(np.int32)
    ref = generate(m, paddle.to_tensor(ids), max_new_tokens=21,
                   decode_window=1).numpy()
    eos = int(ref[0, 8])
    re = generate(m, paddle.to_tensor(ids), max_new_tokens=21,
                  eos_token_id=eos, decode_window=1).numpy()
    we = generate(m, paddle.to_tensor(ids), max_new_tokens=21,
                  eos_token_id=eos, decode_window=8).numpy()
    # identical shape AND tokens: windowed eos truncation must land on
    # the same column as the scalar path
    assert we.shape == re.shape
    np.testing.assert_array_equal(re, we)
    # window larger than remaining tokens (tail window path)
    w = generate(m, paddle.to_tensor(ids), max_new_tokens=5,
                 decode_window=16).numpy()
    np.testing.assert_array_equal(w, ref[:, :12])
