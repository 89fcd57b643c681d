"""The lfm2_moe family (``models/lfm2.py``) and the dropless sparse
block (``incubate/distributed/models/moe.py``) against the benchmark's
plain reference (``perf/reference/lfm2_moe.py``), at toy widths on the
CPU in float32.

Tolerances.  Both sides compute in float32 (the reference under
``highest`` matmul precision, the CPU backend's own), in different
orders of summation: 2e-5 relative to the largest entry holds logits,
outputs and gradients (observed at most 3e-6), and would not hold a
bfloat16 anywhere in the path (2^-8 = 4e-3).
"""
import functools
import gc
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
from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2ShortConv  # noqa: E402
from paddle_tpu.nn import functional as F  # noqa: E402
from perf.models import common as M  # noqa: E402
from perf.models import lfm2_moe as A  # noqa: E402
from perf.reference import common as C  # noqa: E402
from perf.reference import lfm2_moe as R  # noqa: E402

TOL = 2e-5
ROUTER, HELD, TOP_K, H, WIDTH = 16, 4, 2, 32, 16

CFG = {
    "family": "lfm2_moe", "hidden_size": H, "intermediate_size": 48,
    "moe_intermediate_size": WIDTH, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 64, "conv_L_cache": 3,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "layers_kept": [1, 2, 3], "num_dense_layers": 1,
    "num_experts": HELD, "published": {"num_experts": ROUTER},
    "expert_offset": 4, "num_experts_per_tok": TOP_K,
    "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000},
    "expert_bias_seed": 3, "expert_bias_std": 0.02,
    "tie_word_embeddings": True,
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
    # the selection bias and the tally are not leaves of a checkpoint
    assert not any("expert_bias" in k or "routed" in k
                   for k in model.state_dict())


def test_conv_operator_alone_and_its_causality():
    cfg = Lfm2MoeConfig(hidden_size=H, layer_types=("conv",))
    conv = Lfm2ShortConv(cfg)
    w = {"conv.in_proj": conv.in_proj.weight._read(),
         "conv.taps": conv.conv_weight._read(),
         "conv.out_proj": conv.out_proj.weight._read()}
    a = np.random.default_rng(0).standard_normal((2, 12, H)).astype("f4")
    got = conv(paddle.to_tensor(a))._read()
    with jax.default_matmul_precision("highest"):
        close(got, R.short_conv(jnp.asarray(a), w, C.Matmul()))
    later = a.copy()
    later[:, 7] += 1.0
    moved = np.asarray(conv(paddle.to_tensor(later))._read())
    assert np.array_equal(moved[:, :7], np.asarray(got)[:, :7])
    assert not np.allclose(moved[:, 7:10], np.asarray(got)[:, 7:10])
    assert np.array_equal(moved[:, 10:], np.asarray(got)[:, 10:])


def test_shifted_multiply_adds_are_the_grouped_convolution():
    rng = np.random.default_rng(1)
    u = rng.standard_normal((2, 10, 6)).astype("f4")
    taps = rng.standard_normal((3, 6)).astype("f4")
    got = F.causal_depthwise_conv1d(paddle.to_tensor(u),
                                    paddle.to_tensor(taps))
    want = F.conv1d(paddle.to_tensor(u.transpose(0, 2, 1)),
                    paddle.to_tensor(taps.T[:, None, :]), padding=2,
                    groups=6)._read()[:, :, :10]
    close(got._read(), jnp.swapaxes(want, 1, 2))


def _block_and_leaves(offset, held, bias, seed=2):
    """A block holding experts offset..offset+held of ROUTER, its
    leaves under the reference's names, drawn from one set of ROUTER
    experts that every share slices."""
    rng = np.random.default_rng(seed)
    full = {"moe.router": rng.standard_normal((H, ROUTER)) * 0.5,
            "moe.w1": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w3": rng.standard_normal((ROUTER, H, WIDTH)) * 0.2,
            "moe.w2": rng.standard_normal((ROUTER, WIDTH, H)) * 0.2}
    full = {k: jnp.asarray(v, jnp.float32) for k, v in full.items()}
    block = SparseMoEBlock(H, WIDTH, ROUTER, TOP_K, expert_offset=offset,
                           experts_held=held, expert_bias=bias,
                           name=f"share_{offset}")
    block.gate.weight._write(full["moe.router"])
    for name in ("w1", "w3", "w2"):
        getattr(block, name)._write(
            full[f"moe.{name}"][offset:offset + held])
    return block, full


def _tokens(n=40, seed=4):
    return np.random.default_rng(seed).standard_normal((n, H)).astype("f4")


def test_every_token_to_one_held_expert_and_none_dropped():
    bias = np.zeros(ROUTER, np.float32)
    bias[6] = 10.0               # every token's first choice, held here
    block, full = _block_and_leaves(4, HELD, bias)
    f = _tokens()
    out, tally, _ = block(paddle.to_tensor(f))
    counts = np.asarray(tally._read())
    assert counts[2] == len(f) and counts[-1] == TOP_K * len(f)
    assert counts[:HELD].sum() >= len(f)
    held = {k: (w[4:4 + HELD] if k != "moe.router" else w)
            for k, w in full.items()}
    with jax.default_matmul_precision("highest"):
        want = R.sparse_ffn(jnp.asarray(f), held, jnp.asarray(bias), TOP_K,
                            1.0, 4, C.Matmul())
    close(out._read(), want)
    # the bias chose the expert and left the weights alone
    assert float(jnp.abs(want).max()) < 10.0


@pytest.mark.parametrize("held", [HELD, ROUTER])
def test_the_shares_sum_to_the_uncut_layer(held):
    """What the ROUTER / held chips of a layer each compute, summed, is
    what the uncut reference gives for the whole layer."""
    bias = 0.3 * np.random.default_rng(9).standard_normal(ROUTER).astype("f4")
    f = _tokens()
    total, slots = 0.0, 0
    for offset in range(0, ROUTER, held):
        block, full = _block_and_leaves(offset, held, bias)
        out, tally, _ = block(paddle.to_tensor(f))
        total = total + np.asarray(out._read(), np.float64)
        slots += int(np.asarray(tally._read())[:held].sum())
    assert slots == TOP_K * len(f)          # every slot on one chip
    with jax.default_matmul_precision("highest"):
        want = R.sparse_ffn(jnp.asarray(f), full, jnp.asarray(bias), TOP_K,
                            1.0, 0, C.Matmul())
    close(total, want)


def _one_held_choice():
    """Every token's first choice is held expert 6 and its second an
    absent one: exactly one slot a token is routed here."""
    bias = np.full(ROUTER, -10.0, np.float32)
    bias[[6, 12]] = 10.0, 5.0
    return bias


def _absent_choices():
    bias = np.zeros(ROUTER, np.float32)
    bias[[0, 13]] = 10.0        # neither among experts 4..8
    return bias


# chunk size (None: the module's), experts held, the selection bias
# (None: a random one), chunks that run of the chunks there are
ROUTINGS = {
    "one_chunk": (None, HELD, None, (1, 1)),
    "groups_cut_at_chunk_edges": (16, HELD, None, None),
    "nothing_routed_here": (16, HELD, _absent_choices, (0, 5)),
    "routed_ends_on_a_chunk_edge": (8, HELD, _one_held_choice, (5, 10)),
    "partial_last_chunk": (16, HELD, _one_held_choice, (3, 5)),
    "every_slot_routed_here": (16, ROUTER, None, (5, 5)),
    "one_slot_a_chunk": (1, HELD, _one_held_choice, (40, 80)),
    # the same, on a kernel that leaves what it likes past the last group
    "garbage_past_the_last_group": (16, HELD, _one_held_choice, (3, 5)),
}


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_block_gradients_reach_router_and_experts_not_the_bias(
        routing, monkeypatch):
    """40 tokens, 80 sorted slots, taken ``slots_at_a_time`` at a time
    as the real size takes thousands: the chunks past the slots routed
    here are skipped, the last that runs may be partly filled, and the
    result and every gradient are the reference's whatever the cut."""
    from paddle_tpu.incubate.distributed.models import moe
    slots_at_a_time, held_n, bias, chunks = ROUTINGS[routing]
    if slots_at_a_time:
        monkeypatch.setattr(moe, "_SLOTS_AT_A_TIME", slots_at_a_time)
    if routing.startswith("garbage"):
        # the CPU's ragged_dot writes zeros past the last group; the
        # chip's kernel writes nothing there, and what is left is not
        # always a number (my chip run, PR 30: NaN gradients)
        plain = jax.lax.ragged_dot

        def ragged_dot(lhs, rhs, group_sizes):
            out = plain(lhs, rhs, group_sizes)
            past = jnp.arange(out.shape[0]) >= group_sizes.sum()
            return jnp.where(past[:, None], jnp.nan, out)

        monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    bias = bias() if bias else \
        0.3 * np.random.default_rng(9).standard_normal(ROUTER).astype("f4")
    offset = 4 if held_n == HELD else 0
    block, full = _block_and_leaves(offset, held_n, bias)
    f = _tokens()
    x = paddle.to_tensor(f)
    x.stop_gradient = False
    out, tally, ran = block(x)
    (out * out).sum().backward()
    held = {k: (w[offset:offset + held_n] if k != "moe.router" else w)
            for k, w in full.items()}

    def ref(fv, w):
        return R.sparse_ffn(fv, w, jnp.asarray(bias), TOP_K, 1.0, offset,
                            C.Matmul())

    with jax.default_matmul_precision("highest"):
        want = ref(jnp.asarray(f), held)
        want_x, want_w = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2),
                                  argnums=(0, 1))(jnp.asarray(f), held)
    grads = {"x": x.grad._read(), "moe.router": block.gate.weight.grad._read(),
             **{f"moe.{n}": getattr(block, n).grad._read()
                for n in ("w1", "w3", "w2")}}
    close(out._read(), want)
    for name, got in grads.items():
        close(got, want_x if name == "x" else want_w[name])
    assert block.expert_bias.stop_gradient
    # the chunks that ran are those that hold a slot routed here
    routed = int(np.asarray(tally._read())[:-1].sum())
    ran, there = (int(v) for v in np.asarray(ran._read()))
    size = TOP_K * len(f) // there
    assert ran == -(-routed // size)
    if chunks:
        assert (ran, there) == chunks
    if not routed:
        for got in (out._read(), *grads.values()):
            assert np.isfinite(np.asarray(got)).all()
            assert not np.asarray(got).any()


@pytest.mark.parametrize("routing", [
    "one_chunk", "partial_last_chunk", "groups_cut_at_chunk_edges",
    "nothing_routed_here"])
def test_kernel_products_are_the_ragged_dot_products(routing, monkeypatch):
    """``sparse_moe``'s value and every gradient with a chunk's
    products made by ``ops/pallas/grouped_matmul.py`` (interpreted) are
    those with ``jax.lax.ragged_dot``, with one live chunk, with
    several, with groups cut at chunk edges and with none live: the
    kernels' zeros past the last group stand where the selects
    stood."""
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.ops.pallas import grouped_matmul
    slots_at_a_time, held_n, bias, chunks = ROUTINGS[routing]
    bias = bias() if bias else \
        0.3 * np.random.default_rng(9).standard_normal(ROUTER).astype("f4")
    block, _ = _block_and_leaves(4, held_n, bias)
    leaves = [jnp.asarray(_tokens())] + [
        p._read() for p in (block.gate.weight, block.w1, block.w3, block.w2)]
    real, calls = grouped_matmul.grouped_dot, []
    monkeypatch.setattr(
        grouped_matmul, "grouped_dot", lambda rows, *a, **kw: calls.append(
            rows.shape) or real(rows, *a, **kw, interpret=True))

    def both(kernel):
        def loss(*a):
            out, tally, ran = moe.sparse_moe(
                *a, bias=jnp.asarray(bias), top_k=TOP_K, expert_offset=4,
                slots_at_a_time=slots_at_a_time, grouped_kernel=kernel)
            return jnp.sum(out ** 2), (out, ran)

        (_, (out, ran)), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*leaves)
        return (out, *grads), ran

    want, ran = both(False)
    assert not calls
    got, _ = both(True)
    assert int(ran[0]) == chunks[0] if chunks else int(ran[0]) > 1
    # forward, the chunk's recompute, and a gradient for each product
    assert len(calls) == 6 and len(set(calls)) == 2
    for g, w in zip(got, want):
        close(g, w)
        assert np.isfinite(np.asarray(g)).all()


@pytest.mark.parametrize("pull", [-10.0, 0.0, 0.6])
def test_run_share_is_the_chunks_that_hold_a_slot_routed_here(
        pull, monkeypatch):
    """``moe.slot_rows_run_share`` is ceil(R / S) * S of the N * k
    sorted slots, R from the call's own tally, at three routed shares
    (the held experts pushed away, left alone, pulled)."""
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(moe, "_SLOTS_AT_A_TIME", 16)
    bias = 0.3 * np.random.default_rng(9).standard_normal(ROUTER).astype("f4")
    bias[4:4 + HELD] += pull
    block, _ = _block_and_leaves(4, HELD, bias)
    f = _tokens()
    _, tally, ran = block(paddle.to_tensor(f))
    block.count(tally, ran)
    routed = int(np.asarray(tally._read())[:-1].sum())
    assert (routed == 0) == (pull == -10.0)
    got = metrics.snapshot()["moe"]["slot_rows_run_share"]["layer=share_4"]
    assert got == pytest.approx(-(-routed // 16) * 16 / (TOP_K * len(f)))
    block.count(tally, ran)     # a second call: the same share of twice
    assert metrics.snapshot()["moe"]["slot_rows_run_share"][
        "layer=share_4"] == pytest.approx(got)
    assert [int(v) for v in block.chunks._read()] == [
        2 * -(-routed // 16), 2 * 5]


def test_one_compiled_step_linearises_as_it_records_and_feeds_the_tally(
        monkeypatch):
    from paddle_tpu import amp
    from paddle_tpu.incubate.distributed.models import moe
    from paddle_tpu.observability import metrics
    monkeypatch.setattr(moe, "_SLOTS_AT_A_TIME", 16)    # 6 chunks a call
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
    by_call = A.expert_calls()
    for layer, block in model.sparse_blocks().items():
        *here, filled = block.tally()
        assert filled == slots and 0 < sum(here) < slots
        # the same by call: three calls, numbered from 1
        calls = by_call[layer]
        assert sorted(calls) == [1, 2, 3]
        assert [sum(c) for c in zip(*calls.values())] == [*here, filled]
        # and the chunks that ran, call by call: the recomputed block
        # counts each call once
        ran = sum(-(-sum(c[:-1]) // 16) for c in calls.values())
        assert [int(v) for v in block.chunks._read()] == [ran, 3 * 6]
        assert 0 < ran < 3 * 6
    want = {layer: (block.tally()[:HELD], block.routed_here_share())
            for layer, block in model.sparse_blocks().items()}
    run_share = {layer: int(block.chunks._read()[0]) / 18
                 for layer, block in model.sparse_blocks().items()}
    # the registry reads the tally's buffer, not the block: a snapshot
    # taken when the model is gone still says what was routed
    del model, opt, train_step, exe, block
    gc.collect()
    tokens, shares = A.expert_counters()
    by_call = A.expert_calls()
    snap = metrics.snapshot()["moe"]
    for layer, (held, share) in want.items():
        assert tokens[layer] == held
        assert shares[layer] == pytest.approx(share)
        assert [sum(c) for c in zip(*by_call[layer].values())][:HELD] == held
        assert snap["tokens_per_expert"][
            f"expert={CFG['expert_offset']},layer={layer}"] == held[0]
        assert snap["slot_rows_run_share"][f"layer={layer}"] == \
            pytest.approx(run_share[layer])


def test_tally_carries_past_a_32_bit_word_and_the_ring_keeps_the_last_calls(
        monkeypatch):
    from paddle_tpu.incubate.distributed.models import moe
    monkeypatch.setattr(moe, "_CALLS_KEPT", 4)
    block, _ = _block_and_leaves(4, HELD, None)
    big = (1 << 30) - 5
    for n in range(1, 7):
        block.count(paddle.to_tensor(
            jnp.full((HELD + 1,), big - n, jnp.int32)),
            paddle.to_tensor(jnp.asarray([1, 2], jnp.int32)))
    assert block.tally() == [6 * big - 21] * (HELD + 1)
    assert moe.routed_by_call()["share_4"] == {
        n: [big - n] * (HELD + 1) for n in (3, 4, 5, 6)}
