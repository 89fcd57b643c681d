"""The mellum family (``models/mellum.py``: sliding-window layers beside
full ones, a rotary table per layer type, a softmax router in the
dropless block, no shared expert) against the benchmark's plain
reference (``perf/reference/mellum.py``), at toy widths that keep the
published ratios (4 query heads over 2 key/value heads of 8, a window of
5 in a row of 24, 8 routed experts top-3, three window layers to one
full) on the CPU in float32.  The flash kernel pair's window is
tests/test_flash_window.py; here attention takes the XLA fallback,
which is given the same ``window``.

Tolerances.  Both sides compute in float32 (the reference under
``highest`` matmul precision, the CPU backend's own), in different
orders of summation: 2e-5 relative to the largest entry holds logits,
outputs and gradients, and would not hold a bfloat16 anywhere in the
path (2^-8 = 4e-3).  About 25 s under the tier-1 command.
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
from paddle_tpu.models.llama import rope_angles, yarn_inv_freq  # noqa: E402
from paddle_tpu.models.mellum import MellumConfig, RopeTables  # noqa: E402
from perf.models import common as M  # noqa: E402
from perf.models import mellum as A  # noqa: E402
from perf.reference import common as C  # noqa: E402
from perf.reference import mellum as R  # noqa: E402

TOL = 2e-5
ROUTER, HELD, TOP_K, H, WIDTH = 8, 2, 3, 32, 16
HEADS, KV, D, WINDOW = 4, 2, 8, 5
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the published groups at a toy's scale: 24 original positions, so that
# the ramp lies inside the 4 pairs of an 8-wide head
ROPE = {"sliding_attention": {"rope_type": "default", "rope_theta": 100.0},
        "full_attention": {"rope_type": "yarn", "rope_theta": 100.0,
                           "factor": 4.0,
                           "original_max_position_embeddings": 24,
                           "beta_fast": 2.0, "beta_slow": 0.5,
                           "attention_factor": 1.1386}}

CFG = {
    "family": "mellum", "hidden_size": H, "intermediate_size": 48,
    "moe_intermediate_size": WIDTH, "num_attention_heads": HEADS,
    "num_key_value_heads": KV, "head_dim": D, "sliding_window": WINDOW,
    "use_sliding_window": True, "attention_bias": False,
    "norm_topk_prob": True, "vocab_size": 64, "layer_types": PERIOD * 2,
    "mlp_layer_types": ["sparse"] * 8, "layers_kept": [0, 1, 2, 3],
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


# One model a ``recompute`` for the cases that leave it as it was, built
# by the first that asks: inside the case, so that
# ``_leave_no_block_behind`` sees its blocks come and go.
seeded = functools.lru_cache(maxsize=None)(_seeded)


def batch(rows=2, seq=24, seed=5):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, CFG["vocab_size"], (rows, seq + 1), dtype=np.int32)
    return tok[:, :-1].copy(), tok[:, 1:].copy()


@functools.lru_cache(maxsize=None)
def reference_side(train_router=True):
    """The reference's logits, loss and gradients on ``batch()``."""
    cfg = dict(CFG, train_router=train_router)
    weights = C.make_weights(R.table(cfg), seed=11)
    ids, labels = batch()
    spec = {"rows": ids.shape[0], "seq_len": ids.shape[1]}
    with jax.default_matmul_precision("highest"):
        logits = R.logits(weights, cfg, jnp.asarray(ids))
        (loss, _), grads = jax.jit(jax.value_and_grad(
            R.train_loss_rows(cfg, spec), has_aux=True))(
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


def test_a_lone_share_leaves_its_router_as_seeded():
    """``train_router`` false, the cell's way: the combine weights are
    constants of the backward on both sides.  The values are the
    trained router's, every gradient agrees with the reference's, the
    routers' are zero to the last bit, and the norm's before a block is
    NOT the trained router's gradient (the weights' own path into the
    stream is cut too, not the router's leaf alone)."""
    model, _ = _seeded(False, dict(CFG, train_router=False))
    assert {b.train_router for b in model.sparse_blocks().values()} \
        == {False}
    ids, labels = batch()
    trained_logits, trained_loss, trained = reference_side()
    want_logits, want_loss, want_grads = reference_side(False)
    assert np.array_equal(want_logits, trained_logits)
    assert float(want_loss) == float(trained_loss)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(labels))
    close(float(loss), float(want_loss))
    loss.backward()
    grads = {n: p.grad._read() for n, p in model.named_parameters()}
    for leaf, want in want_grads.items():
        got = grads[A.program_name(leaf, None)]
        close(got, want)
        if leaf.endswith("moe.router"):
            assert not np.asarray(got).any() and not np.asarray(want).any()
            assert np.asarray(trained[leaf]).any()
    moved = [leaf for leaf, want in want_grads.items()
             if np.abs(np.asarray(want) - np.asarray(trained[leaf])).max()
             > 1e-3 * np.abs(np.asarray(want)).max()]
    assert {"layers.0.ffn_norm", "layers.3.ffn_norm"} <= set(moved)


def test_table_names_every_parameter_once_and_no_layer_has_what_it_lacks():
    model, weights = seeded(False)
    names = [A.program_name(k, None) for k in weights]
    assert sorted(names) == sorted(n for n, _ in model.named_parameters())
    assert model.num_params() == sum(w.size for w in weights.values()) \
        == R.parameters(CFG)
    assert "lm_head.weight" in names
    # every layer sparse; no shared expert is built, not even an empty
    # one; the attention lies under the attribute its type gives it
    layers = model.model.layers
    assert [layer.is_sparse for layer in layers] == [True] * 4
    assert not any(hasattr(layer, "shared_expert") or hasattr(layer, "mlp")
                   for layer in layers)
    assert not any("shared" in n or ".mlp." in n for n in names)
    assert [layer._operator for layer in layers] == [
        "window_attention"] * 3 + ["full_attention"]
    assert [getattr(layer, layer._operator).window for layer in layers] \
        == [WINDOW] * 3 + [None]
    assert {block.scoring for block in model.sparse_blocks().values()} \
        == {"softmax"}
    # a stack makes each of its two tables once, whatever its depth
    tables = {id(getattr(layer, layer._operator)._tables)
              for layer in layers}
    assert len(tables) == 1
    made = getattr(layers[0], "window_attention")._tables
    for kind in ("sliding_attention", "full_attention"):
        assert made.get(kind, 24) is made.get(kind, 24)
    assert set(made._made) == {("full_attention", 24),
                               ("sliding_attention", 24)}
    # layers that do not follow the period from its start have no names
    with pytest.raises(ValueError, match="published period"):
        A._model(dict(CFG, layers_kept=[1, 2, 3, 4]))


def test_a_window_layer_forgets_what_left_its_window():
    """The first layer alone (a window of 5): a position's result moves
    with the 5 positions up to it and with no other; the full layer's
    moves with every earlier one."""
    model, _ = seeded(False)
    window, full = (getattr(model.model.layers[i], name)
                    for i, name in ((0, "window_attention"),
                                    (3, "full_attention")))
    a = np.random.default_rng(0).standard_normal((1, 24, H)).astype("f4")
    moved = a.copy()
    moved[:, 10] += 1.0
    for op, reach in ((window, WINDOW), (full, 24)):
        was = np.asarray(op(paddle.to_tensor(a))._read())
        now = np.asarray(op(paddle.to_tensor(moved))._read())
        changed = np.abs(now - was).max(axis=(0, 2)) > 0
        assert changed.tolist() == [10 <= i < 10 + reach for i in range(24)]


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
    assert sorted(model.sparse_blocks()) == [f"layer_{i}" for i in range(4)]
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
                      "o_proj"):
            assert f"checkpoint/{kind}/{inner}/" in hlo, (kind, inner)
        assert "backward/MellumForCausalLM/model/layer_3" in hlo


# ------------------------------------------------------ the rotary tables
def test_the_yarn_table_against_numbers_written_here():
    """The published full-attention group at the published head width:
    the correction range is pairs 18 to 35 of 64 (128 ln(8192 / (2 pi
    r)) / (2 ln 500000) is 18.08 at 32 turns and 34.98 at 1), so pairs
    0-18 turn at the plain frequency, pairs 35-63 at a sixteenth of it,
    and between them by the ramp (i - 18) / 17: pair 19 at 257 / 272 of
    the plain one."""
    inv = yarn_inv_freq(128, 500000, 16, 8192, 32, 1)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    assert inv.shape == (64,) and inv.dtype == np.float64
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-15)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-15)
    assert inv[19] == pytest.approx(plain[19] * 257 / 272, rel=1e-14)
    for i, want in ((1, 0.8146172338565447), (18, 0.024955408670558694),
                    (19, 0.019208015577607828), (26, 0.0027043825167258223),
                    (34, 0.00011040869063028003), (35, 4.7781061769823416e-05),
                    (63, 1.5344629944572555e-07)):
        assert inv[i] == pytest.approx(want, rel=1e-12), i
    # the reference writes the formulas out again and agrees
    ref, factor = R.inv_freq(128, MellumConfig().rope_parameters[
        "full_attention"])
    np.testing.assert_allclose(ref, inv, rtol=1e-14)
    assert factor == 1.2772588722239782
    # cos and sin are both scaled, so position 0 reads the factor itself
    cos, sin = RopeTables(MellumConfig()).get("full_attention", 4)
    assert cos.shape == sin.shape == (4, 128)
    np.testing.assert_allclose(cos[0], 1.2772588722239782, rtol=1e-7)
    np.testing.assert_allclose(sin[0], 0.0)
    np.testing.assert_allclose(cos[3, 19], 1.2772588722239782
                               * np.cos(3 * inv[19]), rtol=1e-6)
    np.testing.assert_allclose(cos[3, 64 + 19], cos[3, 19])   # halves
    # the window layers' table is the plain one the other families make
    plain_cos, plain_sin = rope_angles(np.arange(4), 128, 500000)
    got_cos, got_sin = RopeTables(MellumConfig()).get("sliding_attention", 4)
    assert np.array_equal(got_cos, plain_cos)
    assert np.array_equal(got_sin, plain_sin)


# --------------------------------------------- the router and the shares
def _block(offset, full):
    block = SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, expert_offset=offset,
                           experts_held=HELD, norm_eps=0.0,
                           scoring="softmax", name=f"share_{offset}")
    block.gate.weight._write(full["moe.router"])
    for name in ("w1", "w3", "w2"):
        getattr(block, name)._write(full[f"moe.{name}"][offset:offset + HELD])
    return block


def _full_layer(rng):
    full = {"moe.router": rng.standard_normal((H, ROUTER)) * 0.5,
            "moe.w1": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w3": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w2": rng.standard_normal((ROUTER, WIDTH, H)) * 0.2}
    return {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}


def test_the_shares_routed_parts_add_up_to_the_uncut_layer():
    """What the ROUTER / HELD chips of a layer each compute of the
    routed experts (offsets 0, 2, 4, 6 at 2 held; the published cut is
    8 shares of 8), summed, is what the uncut reference gives for the
    whole layer: there is no shared expert to count once."""
    rng = np.random.default_rng(2)
    full = _full_layer(rng)
    f = rng.standard_normal((40, H)).astype("f4")
    routed, slots = 0.0, 0
    for offset in range(0, ROUTER, HELD):
        part, tally, _ = _block(offset, full)(paddle.to_tensor(f))
        routed = routed + np.asarray(part._read(), np.float64)
        slots += int(np.asarray(tally._read())[:HELD].sum())
    assert slots == TOP_K * len(f)          # every slot on one chip
    uncut = dict(CFG, num_experts=ROUTER, expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want = R.routed_ffn(jnp.asarray(f), full, uncut, C.Matmul())
    close(routed, want)
    # one share is not the layer
    with pytest.raises(AssertionError):
        close(np.asarray(part._read()), want)


def test_the_softmax_router_against_a_hand_count():
    """Three tokens whose logits are written here: the weights are the
    top-3 of a softmax over ALL 8, divided by their own sum (so the
    five left out only decide the selection), and a sigmoid block on the
    same logits weighs them otherwise."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0, 3.0, -2.0, 0.5, -0.5],
                       [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, 1.0, 2.0],
                       [-3.0, 4.0, 4.0, -3.0, 1.0, 1.5, 0.0, 0.0]], "f4")
    top = [[4, 0, 1], [7, 6], [1, 2, 5]]
    want = np.zeros((3, ROUTER))
    for t, chosen in enumerate(top):
        if t == 1:
            continue
        e = np.exp(logits[t, chosen].astype(np.float64))
        want[t, chosen] = e / e.sum()
    # token 1: experts 7 and 6, then a tie the top-k breaks by index
    e = np.exp(np.array([2.0, 1.0, 0.0]))
    want[1, [7, 6, 0]] = e / e.sum()
    x = np.eye(3, H, dtype="f4")            # token t picks the gate's row t
    gate = np.zeros((H, ROUTER), "f4")
    gate[:3] = logits
    got = R.route(jnp.asarray(x), jnp.asarray(gate), TOP_K, C.Matmul())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got).sum(-1), 1.0, rtol=1e-6)
    # the block: identity experts would need H == WIDTH; its weights are
    # read through the result of experts that return their weight's sum
    rng = np.random.default_rng(4)
    full = dict(_full_layer(rng), **{"moe.router": jnp.asarray(gate)})
    outs = {}
    for scoring in ("softmax", "sigmoid"):
        block = SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, norm_eps=0.0,
                               scoring=scoring, name=f"hand_{scoring}")
        block.gate.weight._write(full["moe.router"])
        for name in ("w1", "w3", "w2"):
            getattr(block, name)._write(full[f"moe.{name}"])
        outs[scoring] = np.asarray(block(paddle.to_tensor(x))[0]._read())
    with jax.default_matmul_precision("highest"):
        experts = np.stack([np.asarray(R.swiglu(
            jnp.asarray(x), full["moe.w1"][e], full["moe.w3"][e],
            full["moe.w2"][e], C.Matmul())) for e in range(ROUTER)], 1)
    close(outs["softmax"], np.einsum("te,teh->th", want, experts))
    sig = 1 / (1 + np.exp(-logits.astype(np.float64)))
    other = np.zeros_like(want)
    for t, chosen in enumerate([[4, 0, 1], [7, 6, 0], [1, 2, 5]]):
        other[t, chosen] = sig[t, chosen] / sig[t, chosen].sum()
    close(outs["sigmoid"], np.einsum("te,teh->th", other, experts))
    assert np.abs(outs["sigmoid"] - outs["softmax"]).max() > 1e-3
    with pytest.raises(ValueError, match="scoring"):
        SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, scoring="tanh")


def test_sigmoid_callers_programs_are_what_they_were():
    """``scoring`` is a choice made while tracing: the default and an
    explicit ``"sigmoid"`` trace to one jaxpr, with a ``logistic`` over
    the logits and no softmax's ``reduce_max`` / ``exp`` in the router;
    the families that never name it get the default."""
    rng = np.random.default_rng(5)
    full = _full_layer(rng)
    x = jnp.asarray(rng.standard_normal((12, H)), jnp.float32)
    args = (x, full["moe.router"], full["moe.w1"][:HELD],
            full["moe.w3"][:HELD], full["moe.w2"][:HELD])
    kw = dict(bias=jnp.zeros(ROUTER), top_k=TOP_K, expert_offset=0)

    def traced(**more):
        return str(jax.make_jaxpr(
            lambda *a: sparse_moe(*a, **kw, **more))(*args))

    default = traced()
    assert default == traced(scoring="sigmoid")
    soft = traced(scoring="softmax")
    assert default != soft
    router = default[:default.index("top_k")]
    assert "logistic" in router and "reduce_max" not in router
    assert "logistic" not in soft[:soft.index("top_k")]
    # so are the lone share's two arguments at their defaults
    assert default == traced(train_router=True, slots_at_a_time=8192)
    assert default != traced(train_router=False)
    from paddle_tpu.models.deepseek_v3 import DeepseekV3Config
    from paddle_tpu.models.kimi_linear import KimiLinearConfig
    for family in (DeepseekV3Config, KimiLinearConfig):
        assert not hasattr(family(), "routed_block")
    block = SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, name="default")
    assert (block.scoring, block.train_router, block.slots_at_a_time) \
        == ("sigmoid", True, None)
    assert MellumConfig().routed_block == dict(
        scoring="softmax", train_router=True, slots_at_a_time=None)


@pytest.mark.parametrize("tokens,at_a_time,ran", [
    (4096, 8192, [1, 2]), (4097, 8192, [2, 2]),
    (4096, 16384, [1, 1]), (4097, 16384, [1, 1])])
def test_a_chunk_that_ends_at_the_expected_load_doubles_the_row_work(
        tokens, at_a_time, ran):
    """8,192 tokens of two slots each, ``tokens`` of them with both
    slots on the two held experts: at the default chunk of 8,192 sorted
    slots one token more than 4,096 runs a second chunk; a chunk of
    16,384 runs one either way, and the result is the same."""
    n, width = 8192, 8
    x = np.zeros((n, width), "f4")
    x[:tokens, 0] = 1.0         # experts 0 and 1, held
    x[tokens:, 1] = 1.0         # experts 2 and 3, absent
    gate = np.zeros((width, 4), "f4")
    gate[0, :2] = 4.0, 2.0
    gate[1, 2:] = 4.0, 2.0
    rng = np.random.default_rng(7)
    w1, w3 = (jnp.asarray(rng.standard_normal((2, width, 4)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.standard_normal((2, 4, width)), jnp.float32)
    out, tally, chunks = jax.jit(functools.partial(
        sparse_moe, bias=jnp.zeros(4), top_k=2, expert_offset=0,
        scoring="softmax", norm_eps=0.0, slots_at_a_time=at_a_time))(
            jnp.asarray(x), jnp.asarray(gate), w1, w3, w2)
    assert [int(v) for v in tally] == [tokens, tokens, 2 * n]
    assert [int(v) for v in chunks] == ran
    p = np.exp(4.0) / (np.exp(4.0) + np.exp(2.0))
    want = np.zeros((n, width))
    with jax.default_matmul_precision("highest"):
        want[:tokens] = sum(weight * np.asarray(R.swiglu(
            jnp.asarray(x[:1]), w1[e], w3[e], w2[e], C.Matmul()))
            for e, weight in ((0, p), (1, 1 - p)))
    close(out, want)
