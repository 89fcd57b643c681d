"""The kimi_linear family (``models/kimi_linear.py``: Kimi Delta
Attention and un-rotated latent attention over the feed-forward half it
shares with ``deepseek_v3``) against the benchmark's plain reference
(``perf/reference/kimi_linear.py``: the delta rule token by token), at
toy widths on the CPU in float32: a dense KDA layer, a sparse KDA layer
and a sparse latent-attention layer (published layers 1, 3 and 4).

Tolerances.  Both sides compute in float32 in different orders of
summation: 2e-5 relative to the largest entry holds logits, outputs and
gradients, and would not hold a bfloat16 anywhere in the path.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.models.deepseek_v3 import DeepseekV3Attention  # noqa: E402
from paddle_tpu.models.kimi_linear import (  # noqa: E402
    KimiDeltaAttention, KimiLinearConfig, KimiLinearDecoderLayer)
from perf.models import common as M  # noqa: E402
from perf.models import kimi_linear as A  # noqa: E402
from perf.reference import common as C  # noqa: E402
from perf.reference import kimi_linear as R  # noqa: E402
from perf.reference import lfm2_moe as R_LFM2  # noqa: E402

TOL = 2e-5
ROUTER, HELD, TOP_K, H, WIDTH = 16, 4, 3, 32, 16
HEADS, KDA_DIM, NOPE, ROPE, VDIM, LATENT = 2, 16, 16, 8, 16, 32

CFG = {
    "family": "kimi_linear", "hidden_size": H, "intermediate_size": 48,
    "moe_intermediate_size": WIDTH, "num_attention_heads": HEADS,
    "linear_attn_config": {
        "full_attn_layers": [4], "kda_layers": [1, 2, 3],
        "head_dim": KDA_DIM, "num_heads": HEADS, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "q_lora_rank": None, "kv_lora_rank": LATENT,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "v_head_dim": VDIM,
    "vocab_size": 64, "first_k_dense_replace": 1, "layers_kept": [1, 3, 4],
    "num_experts": HELD, "num_shared_experts": 1,
    "published": {"num_experts": ROUTER}, "expert_offset": 4,
    "num_experts_per_token": TOP_K, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "expert_bias_seed": 3, "expert_bias_std": 0.02,
    "A_log_std": 0.5, "dt_bias_std": 2.0, "kda_chunk": 16,
    "tie_word_embeddings": False,
}


@pytest.fixture(autouse=True)
def _leave_no_block_behind():
    """A block built here is found by ``moe.routed_by_call()`` and by
    the registry's ``moe.*`` gauges long after its test: other files'
    tests, in the same process, read every layer's."""
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    reg = metrics.registry()
    rings, gauges = dict(moe._calls_of), set(reg._metrics)
    yield
    moe._calls_of.clear()
    moe._calls_of.update(rings)
    for key in set(reg._metrics) - gauges:
        if key[0].startswith("moe."):
            del reg._metrics[key]


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    gap = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert gap <= tol, gap


def _seeded(recompute):
    """(the program's model, the reference's leaves) on one seed."""
    weights = C.make_weights(R.table(CFG), seed=11)
    model = A._model(CFG, recompute=recompute,
                     recompute_policy="dots_and_kernels_saveable")
    M.load_weights(model, M.unstack(weights, A.program_name))
    return model, weights


# One model a ``recompute`` for the cases that leave it as it was
# (parameters, buffers, no gradients), built by the first that asks: inside
# the case, so that ``_leave_no_block_behind`` sees its blocks come and go.
# A case that trains a model or reads its tally builds its own (``_seeded``).
seeded = functools.lru_cache(maxsize=None)(_seeded)


def batch(rows=2, seq=24, seed=5):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], (rows, seq + 1), dtype=np.int32)
    return tok[:, :-1].copy(), tok[:, 1:].copy()


@functools.lru_cache(maxsize=None)
def reference_side():
    """The reference's logits, loss and gradients on ``batch()``."""
    weights = C.make_weights(R.table(CFG), seed=11)
    ids, labels = batch()
    spec = {"rows": ids.shape[0], "seq_len": ids.shape[1]}
    loss_rows = R.train_loss_rows(CFG, spec)

    def both(w, ids, labels):       # one program: one compile
        return loss_rows(w, ids, labels)[0], R.logits(w, CFG, ids)

    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            both, has_aux=True))(weights, jnp.asarray(ids),
                                 jnp.asarray(labels))
    return logits, loss, grads


# ------------------------------------------------------- the whole model
@pytest.mark.parametrize("recompute", [False, True])
def test_logits_loss_and_every_gradient(recompute):
    """24 positions in chunks of 16: a chunk edge and a padded tail in
    every KDA layer."""
    model, _ = seeded(recompute)
    ids, labels = batch()
    want_logits, want_loss, want_grads = reference_side()
    model.eval()
    close(model(paddle.to_tensor(ids))._read(), want_logits)
    model.train()
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
    close(float(loss), float(want_loss))
    loss.backward()
    grads = {n: p.grad._read() for n, p in model.named_parameters()}
    model.clear_gradients()     # the model is the file's (``seeded``)
    assert set(grads) == {A.program_name(k, None) for k in want_grads}
    for leaf, want in want_grads.items():
        close(grads[A.program_name(leaf, None)], want)


def test_table_names_every_parameter_once_and_the_plan_is_the_published():
    model, weights = seeded(False)
    names = [A.program_name(k, None) for k in weights]
    assert sorted(names) == sorted(n for n, _ in model.named_parameters())
    assert model.num_params() == sum(w.size for w in weights.values())
    assert R.plan(CFG) == [("kda", "dense"), ("kda", "sparse"),
                           ("mla", "sparse")]
    kinds = [(hasattr(layer, "linear_attention"),
              hasattr(layer, "latent_attention"), hasattr(layer, "mlp"),
              hasattr(layer, "shared_expert"))
             for layer in model.model.layers]
    assert kinds == [(True, False, True, False), (True, False, False, True),
                     (False, True, False, True)]
    assert "lm_head.weight" in names
    # the decays a step of the seeded gate spread over (0, 1)
    a_log = np.asarray(weights["layers.0.kda.A_log"])[:, None]
    dt = np.asarray(weights["layers.0.kda.dt_bias"]).reshape(HEADS, -1)
    decay = np.exp(-np.exp(a_log) * np.log1p(np.exp(dt)))
    assert decay.min() < 0.3 and decay.max() > 0.9


# ------------------------------------------------ the operators, alone
def _kda_operator(seed=0):
    cfg = KimiLinearConfig(hidden_size=H, layer_types=("kda",),
                           num_heads=HEADS, kda_head_dim=KDA_DIM,
                           kda_chunk=16)
    paddle.seed(seed)
    op = KimiDeltaAttention(cfg)
    rng = np.random.default_rng(seed)
    # a head norm that is not the identity, a gate that is not constant
    op.o_norm._write(jnp.asarray(1.0 + 0.1 * rng.standard_normal(KDA_DIM),
                                 jnp.float32))
    op.A_log._write(jnp.asarray(0.5 * rng.standard_normal(HEADS),
                                jnp.float32))
    op.dt_bias._write(jnp.asarray(
        2.0 * rng.standard_normal(HEADS * KDA_DIM), jnp.float32))
    w = {A_leaf: getattr_path(op, name[len("linear_attention."):])
         for A_leaf, name in A._LEAVES.items() if A_leaf.startswith("kda.")}
    return op, {k: v._read() for k, v in w.items()}


def getattr_path(obj, path):
    for part in path.split("."):
        obj = getattr(obj, part)
    return obj


def test_kimi_delta_attention_alone_and_its_causality():
    op, w = _kda_operator()
    a = np.random.default_rng(0).standard_normal((2, 36, H)).astype("f4")
    got = op(paddle.to_tensor(a))._read()
    with jax.default_matmul_precision("highest"):
        want = R.kda(jnp.asarray(a), w, CFG, C.Matmul())
    assert got.shape == (2, 36, H)
    close(got, want)
    # a position's result does not see the positions after it, across
    # a chunk's edge or inside a chunk
    later = a.copy()
    later[:, 20:] += 1.0
    moved = op(paddle.to_tensor(later))._read()
    close(moved[:, :20], got[:, :20], tol=1e-6)
    assert not np.allclose(moved[:, 20:], got[:, 20:])


def test_latent_attention_without_rotation():
    """``rotate`` false: the "rope" dimensions of q and of the one
    shared key head go into the scores as the projections made them,
    and a score no longer depends on where its two positions lie."""
    cfg = KimiLinearConfig(hidden_size=H, layer_types=("mla",),
                           num_heads=HEADS, kv_lora_rank=LATENT,
                           qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE,
                           v_head_dim=VDIM)
    assert cfg.rotate is False
    paddle.seed(1)
    op = DeepseekV3Attention(cfg)
    op.kv_norm.weight._write(1.0 + 0.1 * jnp.arange(LATENT, dtype=jnp.float32)
                             / LATENT)
    w = {"attn.q": op.q_proj.weight, "attn.kv_down": op.kv_down.weight,
         "attn.kv_norm": op.kv_norm.weight, "attn.kv_up": op.kv_up.weight,
         "attn.o": op.o_proj.weight}
    w = {k: v._read() for k, v in w.items()}
    a = np.random.default_rng(0).standard_normal((2, 20, H)).astype("f4")
    got = op(paddle.to_tensor(a))._read()
    with jax.default_matmul_precision("highest"):
        want = R.latent_attention(jnp.asarray(a), w, CFG, C.Matmul())
    close(got, want)
    # the rotated operator of deepseek_v3 is another function
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    turned = DeepseekV3Attention(DeepseekV3Config(
        hidden_size=H, num_layers=1, num_heads=HEADS, kv_lora_rank=LATENT,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VDIM,
        kv_norm_eps=cfg.kv_norm_eps))
    for name, p in op.named_parameters():
        getattr_path(turned, name)._write(p._read())
    with pytest.raises(AssertionError):
        close(turned(paddle.to_tensor(a))._read(), got, tol=1e-3)


# -------------------------------------- the shares and the shared expert
def _layer_share(offset, full, bias):
    """A sparse KDA layer holding routed experts offset..offset + HELD
    of ROUTER, its feed-forward leaves sliced from ``full``."""
    layer = KimiLinearDecoderLayer(KimiLinearConfig(
        hidden_size=H, layer_types=("kda", "kda"), num_heads=HEADS,
        kda_head_dim=KDA_DIM, moe_intermediate_size=WIDTH,
        n_shared_experts=1, n_routed_experts=ROUTER,
        num_experts_per_tok=TOP_K, expert_offset=offset, experts_held=HELD,
        expert_bias=(bias,)), 1)
    block = layer.routed_experts
    block.gate.weight._write(full["moe.router"])
    for name in ("w1", "w3", "w2"):
        getattr(block, name)._write(full[f"moe.{name}"][offset:offset + HELD])
    for name, part in (("w1", "gate_proj"), ("w3", "up_proj"),
                       ("w2", "down_proj")):
        getattr(layer.shared_expert, part).weight._write(
            full[f"shared.{name}"])
    return layer


def test_the_shares_sum_with_the_shared_expert_once_to_the_uncut_layer():
    """What the four chips of a layer each compute of the 16 routed
    experts (offsets 0, 4, 8, 12 at 4 held), summed, plus the shared
    expert counted ONCE (every chip computes it alike), is what the
    uncut reference gives for the whole feed-forward."""
    rng = np.random.default_rng(2)
    full = {"moe.router": rng.standard_normal((H, ROUTER)) * 0.5,
            "moe.w1": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w3": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w2": rng.standard_normal((ROUTER, WIDTH, H)) * 0.2,
            "shared.w1": rng.standard_normal((H, WIDTH)) * 0.2,
            "shared.w3": rng.standard_normal((H, WIDTH)) * 0.2,
            "shared.w2": rng.standard_normal((WIDTH, H)) * 0.2}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    bias = 0.3 * rng.standard_normal(ROUTER).astype("f4")
    f = rng.standard_normal((40, H)).astype("f4")
    routed, shared, slots = 0.0, [], 0
    for offset in range(0, ROUTER, HELD):
        layer = _layer_share(offset, full, bias)
        part, tally, _ = layer.routed_experts(paddle.to_tensor(f))
        routed = routed + np.asarray(part._read(), np.float64)
        slots += int(np.asarray(tally._read())[:HELD].sum())
        shared.append(np.asarray(layer.shared_expert(
            paddle.to_tensor(f))._read()))
    assert slots == TOP_K * len(f)          # every slot on one chip
    for other in shared[1:]:                # every chip computes it alike
        assert np.array_equal(other, shared[0])
    uncut = dict(CFG, expert_offset=0)
    mm = C.Matmul()
    with jax.default_matmul_precision("highest"):
        want = R.routed_ffn(jnp.asarray(f), full, jnp.asarray(bias), uncut,
                            mm) + R_LFM2.swiglu(
            jnp.asarray(f), full["shared.w1"], full["shared.w3"],
            full["shared.w2"], mm)
    close(routed + shared[0], want)
    # counted four times it is not the layer
    with pytest.raises(AssertionError):
        close(routed + 4 * shared[0].astype(np.float64), want)


def test_the_reference_s_delta_rule_is_the_recurrence_whatever_the_block():
    """The reference's nested scans (blocks of 64, of 5, one token)
    give the same numbers: its checkpoints change no value."""
    rng = np.random.default_rng(4)
    q, k, v, g = (jnp.asarray(rng.standard_normal((1, 20, 2, 8)), jnp.float32)
                  for _ in range(4))
    g = -jnp.abs(g)
    beta = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((1, 20, 2)),
                                      jnp.float32))
    whole = R.delta_rule(q, k, v, g, beta)
    for block in (5, 1):
        close(R.delta_rule(q, k, v, g, beta, block=block), whole, tol=1e-6)


def test_the_reference_s_head_groups_add_up_to_the_operator(monkeypatch):
    """The reference runs an operator eight heads at a time to bound its
    memory (the toy's two heads are one group): a head at a time, the
    groups' parts add up to the same result and the same gradients."""
    _, w = _kda_operator()
    rng = np.random.default_rng(3)
    w.update({f"attn.{k}": jnp.asarray(rng.standard_normal(shape) * 0.2,
                                       jnp.float32)
              for k, shape in (("q", (H, HEADS * (NOPE + ROPE))),
                               ("kv_down", (H, LATENT + ROPE)),
                               ("kv_norm", (LATENT,)),
                               ("kv_up", (LATENT, HEADS * (NOPE + VDIM))),
                               ("o", (HEADS * VDIM, H)))})
    a = jnp.asarray(rng.standard_normal((1, 20, H)), jnp.float32)
    for op in (R.kda, R.latent_attention):
        def both(a, w):     # a new function a call: traced at the
            # group size of the moment, as one program and not op by op
            return jax.jit(jax.value_and_grad(
                lambda a, w: jnp.sum(op(a, w, CFG, C.Matmul()) ** 2),
                argnums=(0, 1)))(a, w)
        whole, (da, dw) = both(a, w)
        monkeypatch.setattr(R, "HEADS_AT_A_TIME", 1)
        parts, (da1, dw1) = both(a, w)
        monkeypatch.undo()
        close(parts, whole)
        close(da1, da)
        for leaf in dw:
            close(dw1[leaf], dw[leaf], tol=5e-5)
