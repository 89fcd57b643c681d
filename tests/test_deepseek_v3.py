"""The deepseek_v3 family (``models/deepseek_v3.py``: latent attention,
a shared expert beside the dropless routed block) against the benchmark's
plain reference (``perf/reference/deepseek_v3.py``), at toy widths that
keep the published ratios (keys 24 = 16 + 8, values 16, latent 32, 8
routed experts top-3, one shared) on the CPU in float32.  The flash
kernel pair at those unequal key / value widths is
tests/test_deepseek_v3_flash.py.

Tolerances.  Both sides compute in float32 (the reference under
``highest`` matmul precision, the CPU backend's own), in different
orders of summation: 2e-5 relative to the largest entry holds logits,
outputs and gradients (observed at most 4e-6), and would not hold a
bfloat16 anywhere in the path (2^-8 = 4e-3).
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
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    SparseMoEBlock)
from paddle_tpu.models.deepseek_v3 import (  # noqa: E402
    DeepseekV3Attention, DeepseekV3Config, DeepseekV3DecoderLayer)
from perf.models import common as M  # noqa: E402
from perf.models import deepseek_v3 as A  # noqa: E402
from perf.reference import common as C  # noqa: E402
from perf.reference import deepseek_v3 as R  # noqa: E402
from perf.reference import lfm2_moe as R_LFM2  # noqa: E402

TOL = 2e-5
ROUTER, HELD, TOP_K, H, WIDTH = 8, 2, 3, 32, 16
HEADS, NOPE, ROPE, VDIM, LATENT = 4, 16, 8, 16, 32

CFG = {
    "family": "deepseek_v3", "hidden_size": H, "intermediate_size": 48,
    "moe_intermediate_size": WIDTH, "num_attention_heads": HEADS,
    "q_lora_rank": None, "kv_lora_rank": LATENT, "qk_nope_head_dim": NOPE,
    "qk_rope_head_dim": ROPE, "v_head_dim": VDIM, "vocab_size": 64,
    "first_k_dense_replace": 1, "layers_kept": [0, 1, 2],
    "n_routed_experts": HELD, "n_shared_experts": 1,
    "published": {"n_routed_experts": ROUTER}, "expert_offset": 2,
    "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "kv_norm_eps": 1e-6, "rope_theta": 50000,
    "expert_bias_seed": 3, "expert_bias_std": 0.02,
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
    with jax.default_matmul_precision("highest"):
        logits = R.logits(weights, CFG, jnp.asarray(ids))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            R.train_loss_rows(CFG, spec), has_aux=True))(
                weights, jnp.asarray(ids), jnp.asarray(labels))
    return logits, loss, grads


# ------------------------------------------------------- the whole model
@pytest.mark.parametrize("recompute", [False, True])
def test_logits_loss_and_every_gradient(recompute):
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


def test_table_names_every_parameter_once():
    model, weights = seeded(False)
    names = [A.program_name(k, None) for k in weights]
    assert sorted(names) == sorted(n for n, _ in model.named_parameters())
    assert model.num_params() == sum(w.size for w in weights.values())
    # the head is a leaf of its own, the selection bias and the tally
    # are not leaves of a checkpoint
    assert "lm_head.weight" in names
    assert not any("expert_bias" in k or "routed_experts.routed" in k
                   for k in model.state_dict())
    # one dense layer, then a shared expert beside every routed block
    kinds = [(hasattr(layer, "mlp"), hasattr(layer, "shared_expert"))
             for layer in model.model.layers]
    assert kinds == [(True, False), (False, True), (False, True)]


def test_one_compiled_step_under_amp_o2_trains_and_feeds_the_tally():
    from paddle_tpu import amp
    model, _ = _seeded(True)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    model, opt = amp.decorate(models=model, optimizers=opt, level="O2",
                              dtype="bfloat16", master_weight=True)

    @paddle.jit.to_static
    def train_step(ids, labels):
        with amp.auto_cast(level="O2", dtype="bfloat16"):
            loss = model(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids, labels = batch()
    losses = [float(train_step(paddle.to_tensor(ids),
                               paddle.to_tensor(labels))) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[2] < losses[0]
    exe, = train_step._cache.values()
    assert exe.tape_nodes.backward == 0 and exe.tape_nodes.record > 0
    slots = 3 * TOP_K * ids.size
    assert sorted(model.sparse_blocks()) == ["layer_1", "layer_2"]
    for layer, block in model.sparse_blocks().items():
        *here, filled = block.tally()
        assert filled == slots and 0 < sum(here) < slots
        assert sorted(A.expert_calls()[layer]) == [1, 2, 3]


# ------------------------------------------------- the operator, alone
def _operator(seed=0):
    cfg = DeepseekV3Config(
        hidden_size=H, num_layers=1, num_heads=HEADS, kv_lora_rank=LATENT,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VDIM)
    paddle.seed(seed)
    op = DeepseekV3Attention(cfg)
    # a latent norm that is not the identity
    op.kv_norm.weight._write(1.0 + 0.1 * jnp.arange(LATENT, dtype=jnp.float32)
                             / LATENT)
    w = {"attn.q": op.q_proj.weight, "attn.kv_down": op.kv_down.weight,
         "attn.kv_norm": op.kv_norm.weight, "attn.kv_up": op.kv_up.weight,
         "attn.o": op.o_proj.weight}
    return op, {k: v._read() for k, v in w.items()}


def test_latent_attention_alone_and_its_causality():
    op, w = _operator()
    a = np.random.default_rng(0).standard_normal((2, 20, H)).astype("f4")
    got = op(paddle.to_tensor(a))._read()
    with jax.default_matmul_precision("highest"):
        want = R.latent_attention(jnp.asarray(a), w, CFG, C.Matmul())
    assert got.shape == (2, 20, H)
    close(got, want)
    # a position's result does not see the positions after it
    later = a.copy()
    later[:, 12:] += 1.0
    moved = op(paddle.to_tensor(later))._read()
    assert np.array_equal(np.asarray(moved[:, :12]), np.asarray(got[:, :12]))
    assert not np.allclose(moved[:, 12:], got[:, 12:])


def test_rope_turns_interleaved_pairs_by_position():
    """The program's roll-and-select form is the reference's rotation of
    the pairs (x[2i], x[2i+1]); position 0 is left alone, and a score
    depends on the distance between two positions only."""
    from paddle_tpu.models.deepseek_v3 import _heads
    from paddle_tpu.models.llama import rope_angles
    cfg = DeepseekV3Config(num_heads=2, qk_nope_head_dim=4,
                           qk_rope_head_dim=8, v_head_dim=4)
    rng = np.random.default_rng(1)
    s = 6
    q = rng.standard_normal((1, s, 2 * 12)).astype("f4")
    kv = rng.standard_normal((1, s, 2 * 8)).astype("f4")
    pe = rng.standard_normal((1, s, 8)).astype("f4")
    cos, sin = (jnp.repeat(t[:, :4], 2, axis=-1)
                for t in rope_angles(np.arange(s), 8, 50000.0))
    qh, kh, vh = (t._read() for t in _heads(
        paddle.to_tensor(q), paddle.to_tensor(kv), paddle.to_tensor(pe),
        cos, sin, cfg))
    assert qh.shape == kh.shape == (1, s, 2, 12) and vh.shape == (1, s, 2, 4)
    want_q = R.rope(jnp.asarray(q).reshape(1, s, 2, 12)[..., 4:], 50000.0)
    want_k = R.rope(jnp.asarray(pe)[:, :, None, :], 50000.0)
    close(qh[..., 4:], want_q)
    for head in range(2):           # the one rotated key head, broadcast
        close(kh[:, :, head, 4:], want_k[:, :, 0])
    close(qh[..., :4], q.reshape(1, s, 2, 12)[..., :4])
    close(kh[..., :4], kv.reshape(1, s, 2, 8)[..., :4])
    close(vh, kv.reshape(1, s, 2, 8)[..., 4:])
    close(qh[:, 0, :, 4:], q.reshape(1, s, 2, 12)[:, 0, :, 4:])


# ------------------------------- the shares and the shared expert (PR 35)
def _layer_share(offset, full, bias):
    """A sparse decoder layer holding routed experts offset..offset +
    HELD of ROUTER, its feed-forward leaves sliced from ``full``."""
    layer = DeepseekV3DecoderLayer(DeepseekV3Config(
        hidden_size=H, num_layers=2, num_heads=HEADS, kv_lora_rank=LATENT,
        qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VDIM,
        moe_intermediate_size=WIDTH, n_shared_experts=1,
        n_routed_experts=ROUTER, num_experts_per_tok=TOP_K,
        expert_offset=offset, experts_held=HELD, expert_bias=(bias,)), 1)
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
    """What the ROUTER / HELD chips of a layer each compute of the
    routed experts (offsets 0, 2, 4, 6 at 2 held), summed, plus the
    shared expert counted ONCE (every chip computes it alike), is what
    the uncut reference gives for the whole feed-forward."""
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


def test_the_normaliser_epsilon_is_the_callers():
    """Scores so small that their sum is near 1e-6: the block given
    1e-20 is this family's reference, the block left at its default is
    LFM2's, and the two differ."""
    rng = np.random.default_rng(3)
    f = rng.standard_normal((24, H)).astype("f4")
    outs = {}
    for eps, route in ((1e-20, R.route), (None, R_LFM2.route)):
        block = SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, name=f"eps_{eps}",
                               **({} if eps is None else {"norm_eps": eps}))
        # logits about -14 for every expert: scores about 8e-7
        gate = np.tile(-14.0 * f[0][:, None] / float(f[0] @ f[0]),
                       (1, ROUTER)).astype("f4")
        block.gate.weight._write(jnp.asarray(gate))
        x = np.tile(f[:1], (24, 1)) + 1e-3 * f
        out, _, _ = block(paddle.to_tensor(x))
        leaves = {k: getattr(block, k)._read() for k in ("w1", "w3", "w2")}
        with jax.default_matmul_precision("highest"):
            w = route(jnp.asarray(x), jnp.asarray(gate),
                      jnp.zeros(ROUTER), TOP_K, 1.0, C.Matmul())
            want = sum(w[:, e, None] * R_LFM2.swiglu(
                jnp.asarray(x), leaves["w1"][e], leaves["w3"][e],
                leaves["w2"][e], C.Matmul()) for e in range(ROUTER))
        close(out._read(), want, tol=1e-4)      # a quotient of tiny numbers
        outs[eps] = float(jnp.abs(w).sum(-1).mean())
    assert outs[1e-20] == pytest.approx(1.0, rel=1e-4)
    assert outs[None] < 0.8
