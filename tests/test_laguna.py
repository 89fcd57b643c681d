"""The laguna family (``models/laguna.py``: head counts and the rotated
share of a head by layer type, a per-head output gate, a shared expert
beside sigmoid-routed ones, a leading dense layer) against the
benchmark's plain reference (``perf/reference/laguna.py``), at toy
widths that keep the published ratios (6 query heads on full layers and
8 on window layers over 2 key/value heads of 8, half a head rotated on
full layers, a window of 5 in a row of 24, 8 routed experts top-3 and
one shared, one full layer to three window ones, layer 0 dense) on the
CPU in float32.  The flash kernel pair's window is
tests/test_flash_window.py; here attention takes the XLA fallback.

Tolerances.  Both sides compute in float32 (the reference under
``highest`` matmul precision, the CPU backend's own), in different
orders of summation: 2e-5 relative to the largest entry holds logits,
outputs and gradients, and would not hold a bfloat16 anywhere in the
path (2^-8 = 4e-3).  About 90 s alone in one process; the toy is
built once (``seeded``), twice more where a case changes it.
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
from paddle_tpu.incubate.distributed.models import moe  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    SparseMoEBlock, sparse_moe)
from paddle_tpu.models.laguna import LagunaConfig  # noqa: E402
from paddle_tpu.models.lfm2 import _rotate  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaMLP  # noqa: E402
from paddle_tpu.models.llama import rope_angles, yarn_inv_freq  # noqa: E402
from paddle_tpu.models.mellum import (MellumAttention,  # noqa: E402
                                      MellumConfig, RopeTables)
from perf.models import common as M  # noqa: E402
from perf.models import laguna as A  # noqa: E402
from perf.reference import common as C  # noqa: E402
from perf.reference import laguna as R  # noqa: E402

TOL = 2e-5
ROUTER, HELD, TOP_K, H, WIDTH = 8, 2, 3, 32, 16
KV, D, WINDOW = 2, 8, 5
PERIOD = ["full_attention"] + ["sliding_attention"] * 3
# the published groups at a toy's scale: 24 original positions, so that
# the ramp lies inside the 2 pairs of the 4 dimensions a full layer
# rotates of its 8
ROPE = {"sliding_attention": {"rope_type": "default", "rope_theta": 100.0,
                              "partial_rotary_factor": 1},
        "full_attention": {"rope_type": "yarn", "rope_theta": 100.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 24,
                           "beta_fast": 2.0, "beta_slow": 0.5,
                           "attention_factor": 1.1386,
                           "partial_rotary_factor": 0.5},
        "original_max_position_embeddings": 24}

CFG = {
    "family": "laguna", "hidden_size": H, "intermediate_size": 48,
    "moe_intermediate_size": WIDTH, "shared_expert_intermediate_size": WIDTH,
    "num_attention_heads": 6,
    "num_attention_heads_per_layer": [6, 8, 8, 8] * 2,
    "num_key_value_heads": KV, "head_dim": D, "sliding_window": WINDOW,
    "attention_bias": False, "gating": True,
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "vocab_size": 64,
    "layer_types": PERIOD * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "layers_kept": [0, 1, 2, 3, 4],
    "num_experts": HELD, "published": {"num_experts": ROUTER},
    "expert_offset": 2, "num_experts_per_tok": TOP_K, "rms_norm_eps": 1e-6,
    "rope_parameters": ROPE, "tie_word_embeddings": False,
    # the whole model's way; the cell's lone share has its own case
    "train_router": True, "expert_slots_at_a_time": None,
}


@pytest.fixture(autouse=True)
def _leave_no_block_behind():
    """A block built here is found by ``moe.routed_by_call()`` and by
    the registry's ``moe.*`` gauges long after its test: other files'
    tests, in the same process, read every layer's."""
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


def _seeded(recompute, cfg=CFG):
    """(the program's model, the reference's leaves) on one seed."""
    weights = C.make_weights(R.table(cfg), seed=11)
    model = A._model(cfg, recompute=recompute,
                     recompute_policy="dots_and_kernels_saveable")
    M.load_weights(model, M.unstack(weights, A.program_name))
    return model, weights


# the one model of the cases that leave it as it was, built by the
# first that asks: inside the case, so that ``_leave_no_block_behind``
# sees its blocks come and go
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
        logits = jax.jit(lambda w, i: R.logits(w, CFG, i))(
            weights, jnp.asarray(ids))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            R.train_loss_rows(CFG, spec), has_aux=True))(
                weights, jnp.asarray(ids), jnp.asarray(labels))
    return logits, loss, grads


# ------------------------------------------------------- the whole model
def test_logits_loss_and_every_gradient():
    """Under the cell's recompute policy: the forward's values are the
    plain ones whatever is saved, and the backward runs the recomputed
    gate and rotation."""
    model, _ = seeded(True)
    ids, labels = batch()
    want_logits, want_loss, want_grads = reference_side()
    from paddle_tpu import ops
    from paddle_tpu.nn import functional as F
    model.train()
    # one forward: the logits, and from them the loss as
    # ``SparseDecoderForCausalLM.forward`` makes it
    logits = model.logits(paddle.to_tensor(ids))
    close(logits._read(), want_logits)
    loss = F.cross_entropy(ops.reshape(logits, [-1, CFG["vocab_size"]]),
                           ops.reshape(paddle.to_tensor(labels), [-1]))
    close(float(loss), float(want_loss))
    loss.backward()
    grads = {n: p.grad._read() for n, p in model.named_parameters()}
    model.clear_gradients()     # the model is the file's (``seeded``)
    assert set(grads) == {A.program_name(k, None) for k in want_grads}
    for leaf, want in want_grads.items():
        close(grads[A.program_name(leaf, None)], want)
    # every gate's gradient is one that is there to compare
    for i in range(5):
        assert np.abs(np.asarray(want_grads[f"layers.{i}.attn.g"])).max() > 0


def test_table_names_every_parameter_once_and_layers_are_their_types():
    model, weights = seeded(True)
    names = [A.program_name(k, None) for k in weights]
    assert sorted(names) == sorted(n for n, _ in model.named_parameters())
    assert model.num_params() == sum(w.size for w in weights.values()) \
        == R.parameters(CFG)
    layers = model.model.layers
    ops = [getattr(layer, layer._operator) for layer in layers]
    # heads, rotated width, window and gate by layer type, read from
    # the built layers
    assert [layer._operator for layer in layers] == [
        "full_attention"] + ["window_attention"] * 3 + ["full_attention"]
    assert [op.num_heads for op in ops] == [6, 8, 8, 8, 6]
    assert [op.num_kv_heads for op in ops] == [KV] * 5
    assert [op._tables.width(op.kind) for op in ops] == [4, 8, 8, 8, 4]
    assert [op.window for op in ops] == [None, WINDOW, WINDOW, WINDOW, None]
    assert [tuple(op.g_proj.weight.shape) for op in ops] == [
        (H, 6), (H, 8), (H, 8), (H, 8), (H, 6)]
    assert [tuple(op.o_proj.weight.shape) for op in ops] == [
        (6 * D, H), (8 * D, H), (8 * D, H), (8 * D, H), (6 * D, H)]
    # layer 0 dense, the rest a shared expert beside the routed ones
    assert [layer.is_sparse for layer in layers] == [False] + [True] * 4
    assert [hasattr(layer, "mlp") for layer in layers] \
        == [True] + [False] * 4
    assert [hasattr(layer, "shared_expert") for layer in layers] \
        == [False] + [True] * 4
    blocks = model.sparse_blocks()
    assert sorted(blocks) == [f"layer_{i}" for i in range(1, 5)]
    assert {(b.scoring, b.routed_scaling_factor, b.norm_eps, b.top_k,
             b.num_experts, b.experts_held, b.expert_offset)
            for b in blocks.values()} \
        == {("sigmoid", 2.5, 1e-20, TOP_K, ROUTER, HELD, 2)}
    # a stack makes each of its two tables once, at its type's width
    tables, = {id(op._tables) for op in ops}
    made = ops[0]._tables
    assert made.get("full_attention", 24)[0].shape == (24, 4)
    assert made.get("sliding_attention", 24)[0].shape == (24, 8)
    assert made.get("full_attention", 24) is made.get("full_attention", 24)
    # layers that do not follow the period from its start have no names
    with pytest.raises(ValueError, match="published period"):
        A._model(dict(CFG, layers_kept=[1, 2, 3, 4]))
    with pytest.raises(ValueError, match="head counts"):
        LagunaConfig(num_heads_per_layer=(48, 64))


def test_the_published_defaults_are_the_published_file():
    """``LagunaConfig()`` is one period at the catalog's numbers, and
    the adapter builds the configuration file's layers from it."""
    cfg = LagunaConfig()
    assert (cfg.layer_types, cfg.num_heads_per_layer) == (
        ("full_attention",) + ("sliding_attention",) * 3, (48, 64, 64, 64))
    assert (cfg.hidden_size, cfg.num_kv_heads, cfg.head_dim,
            cfg.sliding_window, cfg.intermediate_size,
            cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor) \
        == (2048, 8, 128, 512, 8192, 512, 256, 8, 2.5)
    tables = RopeTables(cfg)
    assert (tables.width("full_attention"),
            tables.width("sliding_attention")) == (64, 128)
    import json
    with open(os.path.join(ROOT, "perf", "configs", "laguna-xs.2.json")) as f:
        cell = json.load(f)
    assert cell["rope_parameters"]["full_attention"] \
        == cfg.rope_parameters["full_attention"]
    assert cell["rope_parameters"]["sliding_attention"] \
        == cfg.rope_parameters["sliding_attention"]
    assert R.plan(cell) == [
        ("full_attention", "dense", 48), ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64),
        ("sliding_attention", "sparse", 64), ("full_attention", "sparse", 48)]
    assert R.parameters(cell) == cell["parameters"] == 691_623_936


# -------------------------------------------- the rotation and its tables
def test_the_yarn_table_at_width_64_against_numbers_written_here():
    """The published full-attention group over the 64 dimensions it
    rotates: the correction range is pairs 5 to 16 of 32 (64 ln(4096 /
    (2 pi t)) / (2 ln 500000) is 5.66 at 64 turns and 15.80 at 1), so
    pairs 0-5 turn at the plain frequency 500000 ** (-i / 32), pairs
    16-31 at a sixty-fourth of it, and between them by the ramp (i - 5)
    / 11: pair 6 at 641 / 704 of the plain one."""
    inv = yarn_inv_freq(64, 500000, 64, 4096, 64, 1)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    assert inv.shape == (32,) and inv.dtype == np.float64
    np.testing.assert_allclose(inv[:6], plain[:6], rtol=1e-15)
    np.testing.assert_allclose(inv[16:], plain[16:] / 64, rtol=1e-15)
    assert inv[6] == pytest.approx(plain[6] * 641 / 704, rel=1e-14)
    for i, want in ((1, 0.6636012376960885), (5, 0.12868737343265052),
                    (6, 0.07775503023178373), (10, 0.009150584078844943),
                    (15, 0.00022400972405040552),
                    (16, 2.209708691207961e-05),
                    (31, 4.709153362717455e-08)):
        assert inv[i] == pytest.approx(want, rel=1e-12), i
    # the reference writes the formulas out again and agrees
    group = LagunaConfig().rope_parameters["full_attention"]
    assert R.rotary_dim(128, group) == 64
    ref, factor = R.inv_freq(64, group)
    np.testing.assert_allclose(ref, inv, rtol=1e-14)
    assert factor == 1.4158883083359672
    # cos and sin are both scaled, so position 0 reads the factor itself
    cos, sin = RopeTables(LagunaConfig()).get("full_attention", 4)
    assert cos.shape == sin.shape == (4, 64)
    np.testing.assert_allclose(cos[0], 1.4158883083359672, rtol=1e-7)
    np.testing.assert_allclose(sin[0], 0.0)
    np.testing.assert_allclose(cos[3, 6], 1.4158883083359672
                               * np.cos(3 * inv[6]), rtol=1e-6)
    np.testing.assert_allclose(cos[3, 32 + 6], cos[3, 6])     # halves
    # the window layers' table is the plain one over the whole head
    plain_cos, plain_sin = rope_angles(np.arange(4), 128, 10000)
    got_cos, got_sin = RopeTables(LagunaConfig()).get("sliding_attention", 4)
    assert np.array_equal(got_cos, plain_cos)
    assert np.array_equal(got_sin, plain_sin)


def test_the_partial_rotation_against_numbers_written_here():
    """A head of 8 of which the first 4 turn: at position 1 with the
    angles (0.5, 0.25), dimension 0 pairs with 2 and 1 with 3 (the
    halves WITHIN the four), and 4-7 pass through untouched."""
    ang = np.array([[0.0, 0.0], [0.5, 0.25]])
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), jnp.float32)
    x = np.arange(1.0, 17.0, dtype="f4").reshape(1, 2, 1, 8)
    q, k = _rotate(paddle.to_tensor(x), paddle.to_tensor(-x), cos, sin)
    q, k = np.asarray(q._read()), np.asarray(k._read())
    assert np.array_equal(q[0, 0], x[0, 0])         # position 0: no turn
    a, b, c, d = x[0, 1, 0, :4].astype(np.float64)  # 9, 10, 11, 12
    want = [a * np.cos(0.5) - c * np.sin(0.5), b * np.cos(0.25)
            - d * np.sin(0.25), c * np.cos(0.5) + a * np.sin(0.5),
            d * np.cos(0.25) + b * np.sin(0.25)]
    np.testing.assert_allclose(q[0, 1, 0, :4], want, rtol=1e-6)
    assert np.array_equal(q[0, 1, 0, 4:], x[0, 1, 0, 4:])
    np.testing.assert_allclose(k, -q, rtol=1e-7)
    # the reference's own, on the toy's full-attention group
    group = ROPE["full_attention"]
    inv, factor = R.inv_freq(4, group)
    got = np.asarray(R.rope(jnp.asarray(x), group))
    assert np.array_equal(got[..., 4:], x[..., 4:])
    want0 = factor * (a * np.cos(inv[0]) - c * np.sin(inv[0]))
    np.testing.assert_allclose(got[0, 1, 0, 0], want0, rtol=1e-6)
    # a table as wide as the head is ``_rotate`` itself
    ang8 = np.linspace(0, 1, 8).reshape(2, 4)
    cos8 = jnp.asarray(np.cos(np.concatenate([ang8, ang8], -1)), jnp.float32)
    sin8 = jnp.asarray(np.sin(np.concatenate([ang8, ang8], -1)), jnp.float32)
    whole = _rotate(paddle.to_tensor(x), paddle.to_tensor(x), cos8,
                          sin8)[0]
    assert np.array_equal(np.asarray(whole._read()), np.asarray(
        _rotate(paddle.to_tensor(x), paddle.to_tensor(x), cos8,
                sin8)[0]._read()))
    assert np.abs(np.asarray(whole._read())[..., 4:] - x[..., 4:]).max() > 0.1


def _pr42_forward(op, x):
    """``MellumAttention.forward`` as PR 42 left it, written out."""
    from paddle_tpu import ops
    from paddle_tpu.core import scope
    from paddle_tpu.nn import functional as F
    b, s, _ = x.shape
    with scope.phase("qkv"):
        q = ops.reshape(op.q_proj(x), [b, s, op.num_heads, op.head_dim])
        k = ops.reshape(op.k_proj(x), [b, s, op.num_kv_heads, op.head_dim])
        v = ops.reshape(op.v_proj(x), [b, s, op.num_kv_heads, op.head_dim])
    with scope.phase("rope"):
        q, k = _rotate(q, k, *op._tables.get(op.kind, s))
    out = F.scaled_dot_product_attention(
        q, k, v, is_causal=True, window=op.window, backend="xla")
    return op.o_proj(ops.reshape(out, [b, s, -1]))


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_mellum2s_arguments_trace_mellum2s_program(kind):
    """With the arguments ``models/mellum.py`` gives it (the config's
    one head count, rope groups without a ``partial_rotary_factor``, no
    gate) the attention has PR 42's parameters and traces PR 42's
    jaxpr; Laguna's arguments trace another."""
    cfg = MellumConfig(hidden_size=H, num_heads=4, num_kv_heads=KV,
                       head_dim=D, sliding_window=WINDOW,
                       use_flash_attention=False)
    tables = RopeTables(cfg)
    op = MellumAttention(cfg, kind, tables)
    assert sorted(n for n, _ in op.named_parameters()) == [
        f"{x}_proj.weight" for x in "koqv"]
    assert (op.num_heads, tables.width(kind)) == (4, D)
    x = jnp.zeros((1, 12, H), jnp.float32)

    def traced(fn):
        return str(jax.make_jaxpr(
            lambda a: fn(paddle.to_tensor(a))._read())(x))

    was = traced(lambda a: _pr42_forward(op, a))
    assert traced(op) == was
    assert "logistic" not in was
    lag = LagunaConfig(hidden_size=H, num_kv_heads=KV, head_dim=D,
                       sliding_window=WINDOW, use_flash_attention=False)
    gated = MellumAttention(lag, kind, RopeTables(lag), num_heads=6,
                            gate=True)
    now = traced(gated)
    assert now != was and "logistic" in now


def test_the_gate_scales_a_heads_part_of_the_result():
    """One window layer alone: with the gate's weights zero every gate
    is a half and the result is half the ungated attention's; a gate
    opened on one head moves that head's rows of ``o_proj`` alone."""
    model, _ = _seeded(False)
    op = model.model.layers[1].window_attention
    a = np.random.default_rng(0).standard_normal((1, 24, H)).astype("f4")
    x = paddle.to_tensor(a)
    was = np.asarray(op.g_proj.weight._read())
    op.g_proj.weight._write(jnp.zeros_like(was))
    half = np.asarray(op(x)._read())
    # the same layer with no gate at all: PR 42's forward
    plain = np.asarray(_pr42_forward(op, x)._read())
    close(half, 0.5 * plain)
    # a large bias-like column: head 3's gate reads the input's first
    # feature times 1e5, so it is 0 or 1 by that feature's sign
    w = np.zeros_like(was)
    w[0, 3] = 1e5
    op.g_proj.weight._write(jnp.asarray(w))
    got = np.asarray(op(x)._read())
    o = np.asarray(op.o_proj.weight._read())        # [heads * D, H]
    only = np.zeros_like(o)
    only[3 * D:4 * D] = o[3 * D:4 * D]
    op.o_proj.weight._write(jnp.asarray(only))
    head3 = np.asarray(_pr42_forward(op, x)._read())
    step = (a[..., :1] > 0).astype("f4")
    close(got - half, (step - 0.5) * head3, tol=1e-4)


def test_one_compiled_step_under_amp_o2_trains_and_feeds_the_tally():
    """The cell's way: a lone share (``train_router`` false, a chunk of
    16,384) through one ``to_static`` step under AMP O2 with the cell's
    recompute policy."""
    from paddle_tpu import amp
    model, _ = _seeded(True, dict(CFG, train_router=False,
                                  expert_slots_at_a_time=16384))
    assert {(b.train_router, b.slots_at_a_time)
            for b in model.sparse_blocks().values()} == {(False, 16384)}
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
    for layer, block in model.sparse_blocks().items():
        *here, filled = block.tally()
        assert filled == slots and 0 < sum(here) < slots
        assert sorted(A.expert_calls()[layer]) == [1, 2, 3]
    # the scopes the per-layer metrics read lie in the compiled program,
    # forward and backward
    tensors = [paddle.to_tensor(ids), paddle.to_tensor(labels)]
    exe = train_step.concrete_program(*tensors)
    hlo = exe.compiled.lower(
        *[t._data for t in tensors + exe.capt_state]).as_text(
            debug_info=True)
    for kind in ("window_attention", "full_attention"):
        for inner in ("qkv/q_proj", "qkv/k_proj", "qkv/v_proj", "rope",
                      "out_gate/g_proj", "out_gate/mul", "o_proj"):
            assert f"checkpoint/{kind}/{inner}" in hlo, (kind, inner)
        # the sigmoid is no product: the policy runs it again
        assert f"rematted_computation/{kind}/out_gate/logistic" in hlo
    assert "backward/LagunaForCausalLM/model/layer_4" in hlo
    assert "/shared_expert/" in hlo and "/mlp/" in hlo


# --------------------------------------------- the router and the shares
def _full_layer(rng):
    full = {"moe.router": rng.standard_normal((H, ROUTER)) * 0.5,
            "moe.w1": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w3": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w2": rng.standard_normal((ROUTER, WIDTH, H)) * 0.2,
            "shared.w1": rng.standard_normal((H, WIDTH)) * 0.2,
            "shared.w3": rng.standard_normal((H, WIDTH)) * 0.2,
            "shared.w2": rng.standard_normal((WIDTH, H)) * 0.2}
    return {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}


def test_the_shares_routed_parts_and_one_shared_expert_are_the_uncut_layer():
    """What the ROUTER / HELD chips of a layer each compute of the
    routed experts (offsets 0, 2, 4, 6 at 2 held; the published cut is
    8 shares of 32), summed, PLUS the shared expert that every chip
    computes alike, counted once, is what the uncut reference gives for
    the whole feed-forward."""
    rng = np.random.default_rng(2)
    full = _full_layer(rng)
    f = rng.standard_normal((40, H)).astype("f4")
    routed, slots = 0.0, 0
    kw = LagunaConfig().routed_block
    for offset in range(0, ROUTER, HELD):
        block = SparseMoEBlock(
            H, WIDTH, ROUTER, TOP_K, expert_offset=offset,
            experts_held=HELD, routed_scaling_factor=2.5,
            norm_eps=R.ROUTER_NORM_EPS, name=f"share_{offset}", **kw)
        block.gate.weight._write(full["moe.router"])
        for name in ("w1", "w3", "w2"):
            getattr(block, name)._write(
                full[f"moe.{name}"][offset:offset + HELD])
        part, tally, _ = block(paddle.to_tensor(f))
        routed = routed + np.asarray(part._read(), np.float64)
        slots += int(np.asarray(tally._read())[:HELD].sum())
    assert slots == TOP_K * len(f)          # every slot on one chip
    shared = LlamaMLP(LlamaConfig(hidden_size=H, num_layers=1,
                                  intermediate_size=WIDTH))
    for name, leaf in (("gate_proj", "w1"), ("up_proj", "w3"),
                       ("down_proj", "w2")):
        getattr(shared, name).weight._write(full[f"shared.{leaf}"])
    once = np.asarray(shared(paddle.to_tensor(f))._read(), np.float64)
    uncut = dict(CFG, num_experts=ROUTER, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want = R.feed_forward(jnp.asarray(f), full, "sparse", uncut,
                              C.Matmul())
    close(routed + once, want)
    # neither the shared expert counted on every chip nor left out
    with pytest.raises(AssertionError):
        close(routed + ROUTER // HELD * once, want)
    with pytest.raises(AssertionError):
        close(routed, want)


def test_the_sigmoid_router_against_a_hand_count():
    """Two tokens whose logits are written here: the weights are 2.5
    times the top-3 sigmoid scores divided by their own sum."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0, 3.0, -2.0, 0.5, -0.5],
                       [-3.0, 4.0, 4.5, -3.0, 1.0, 1.5, 0.0, 0.0]], "f4")
    want = np.zeros((2, ROUTER))
    for t, chosen in enumerate([[4, 0, 1], [2, 1, 5]]):
        s = 1 / (1 + np.exp(-logits[t, chosen].astype(np.float64)))
        want[t, chosen] = 2.5 * s / s.sum()
    x = np.eye(2, H, dtype="f4")            # token t picks the gate's row t
    gate = np.zeros((H, ROUTER), "f4")
    gate[:2] = logits
    got = R.route(jnp.asarray(x), jnp.asarray(gate), 0.0, TOP_K, 2.5,
                  C.Matmul())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 2.5, rtol=1e-6)


def test_the_blocks_callers_programs_are_what_they_were():
    """Laguna asks the block for what its default already is (sigmoid
    scores, a router that trains, the block's own chunk) unless the
    configuration is a lone share's; the families that never name these
    get the default, and Mellum's softmax is as it was."""
    rng = np.random.default_rng(5)
    full = _full_layer(rng)
    x = jnp.asarray(rng.standard_normal((12, H)), jnp.float32)
    args = (x, full["moe.router"], full["moe.w1"][:HELD],
            full["moe.w3"][:HELD], full["moe.w2"][:HELD])
    kw = dict(bias=jnp.zeros(ROUTER), top_k=TOP_K, expert_offset=0)

    def traced(**more):
        return str(jax.make_jaxpr(
            lambda *a: sparse_moe(*a, **kw, **more))(*args))

    assert LagunaConfig().routed_block == dict(
        scoring="sigmoid", train_router=True, slots_at_a_time=None)
    assert MellumConfig().routed_block == dict(
        scoring="softmax", train_router=True, slots_at_a_time=None)
    default = traced()
    assert default == traced(**LagunaConfig().routed_block)
    assert default != traced(**MellumConfig().routed_block)
    lone = LagunaConfig(train_router=False,
                        expert_slots_at_a_time=16384).routed_block
    assert lone == dict(scoring="sigmoid", train_router=False,
                        slots_at_a_time=16384)
    assert default != traced(**lone)
