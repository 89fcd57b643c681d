"""The training driver end to end at a tiny size on the CPU, its check
against the plain reference, and the two ways the check has been shown
to fail: a lower-precision control and a broken timed path."""
import numpy as np
import pytest

import perf_testlib as L

FAMILIES = ["gpt2", "bert"]


def _driver():
    from perf import loader
    return loader.module("drivers", "train_loop")


@pytest.fixture(scope="module")
def runs():
    """One run of the driver per family, shared by the tests below."""
    cache = {}

    def get(family):
        if family not in cache:
            ctx = L.train_context(family, seed=1)
            cache[family] = (ctx, _driver().run(ctx))
        return cache[family]
    return get


@pytest.mark.parametrize("family", FAMILIES)
def test_train_loop_end_to_end(runs, family):
    ctx, run = runs(family)
    assert run.correct and run.failed == 0 and run.attempted > 0
    assert run.end_to_end["train_tokens_per_s"] > 0
    assert run.end_to_end["setup_s"] > 0
    c = run.counters
    assert c["steps"] == run.attempted and c["window_s"] >= ctx.seconds
    assert c["tokens_per_step"] == 4 * 32
    assert run.end_to_end["train_tokens_per_s"] == pytest.approx(
        c["steps"] * c["tokens_per_step"] / c["window_s"])
    assert any('"programs_compiled_in_window": 0' in n for n in run.notes)
    assert any('"step_programs": 1' in n for n in run.notes)


@pytest.mark.parametrize("family", FAMILIES)
def test_lower_precision_control_is_not_correct(family, capsys):
    """The reference computed with fp8 operands, put in the program's
    place, fails the cell's limits; the reference itself passes them."""
    from perf import check, traffic_gen
    drv = _driver()
    ctx = L.train_context(family, seed=1)
    pool = traffic_gen.train_batches(
        ctx.traffic["batch"], ctx.cfg["data_vocab_size"], ctx.seed, 3)
    ref = drv.reference_steps(ctx, pool)
    same = check.Checks(ctx.limits)
    check.train_checks(same, ref, ref)
    assert same.correct
    control = check.Checks(ctx.limits)
    check.train_checks(control, drv.reference_steps(ctx, pool, "fp8"), ref)
    assert not control.correct
    assert not control.as_dict()["first_grad_sketch_gap"]["ok"]


def test_broken_step_is_not_correct(monkeypatch):
    """A step that returns its state unchanged (after the first, which
    makes the state) passes the loss and the first gradient and is
    caught by the parameters' change."""
    from perf.models import common as M
    real = M.TrainProgram.step
    calls = []

    def broken(self, tensors):
        calls.append(1)
        if len(calls) == 1:
            return real(self, tensors)
        self.model.eval()           # forward only, nothing updated
        try:
            return self.model(*tensors)
        finally:
            self.model.train()

    monkeypatch.setattr(M.TrainProgram, "step", broken)
    ctx = L.train_context("gpt2", seed=1)
    run = _driver().run(ctx)
    assert not run.correct
    import json
    checks = next(json.loads(n)["checks"] for n in run.notes
                  if '"checks"' in n)
    assert all(checks[f"loss_gap_step{i}"]["ok"] for i in (1, 2, 3))
    assert checks["first_grad_norm_gap"]["ok"]
    assert checks["first_grad_sketch_gap"]["ok"]
    assert not checks["param_change_norm_gap"]["ok"]


def test_gpt2_reference_is_the_programs_float32_forward():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from perf import traffic_gen
    from perf.models import common as M
    from perf.reference import common as C
    ctx = L.train_context("gpt2", seed=5)
    cfg, spec = ctx.cfg, ctx.traffic["batch"]
    weights = C.make_weights(ctx.reference.table(cfg), ctx.seed)
    model = ctx.models.build_serve(cfg)
    M.load_weights(model, M.unstack(weights, ctx.models.program_name))
    ids, labels = traffic_gen.train_batches(
        spec, cfg["data_vocab_size"], ctx.seed, 1)[0]
    got = float(model(paddle.to_tensor(ids), paddle.to_tensor(labels)))
    with jax.default_matmul_precision("highest"):
        want, _ = ctx.reference.train_loss_rows(cfg, spec)(
            weights, jnp.asarray(ids), jnp.asarray(labels))
        logits = ctx.reference.logits(weights, cfg, jnp.asarray(ids))
    assert got == pytest.approx(float(want), abs=2e-5)
    np.testing.assert_allclose(
        model(paddle.to_tensor(ids)).numpy(), np.asarray(logits), atol=2e-5)


def test_bert_reference_is_the_programs_float32_forward():
    """Equal to rounding with the program's tanh GELU; the published
    erf GELU, which the benchmark's reference computes, differs from
    the program's by what that approximation is worth."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from perf import traffic_gen
    from perf.models import common as M
    from perf.reference import common as C
    ctx = L.train_context("bert", seed=5)
    cfg, spec = ctx.cfg, ctx.traffic["batch"]
    weights = C.make_weights(ctx.reference.table(cfg), ctx.seed)
    model = BertForPretraining(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        max_predictions=spec["masked_per_row"]))
    model.eval()
    M.load_weights(model, M.unstack(weights, ctx.models.program_name))
    batch = traffic_gen.train_batches(
        spec, cfg["data_vocab_size"], ctx.seed, 1)[0]
    got = float(model(*(paddle.to_tensor(a) for a in batch)))
    rows = tuple(jnp.asarray(a) for a in batch)
    with jax.default_matmul_precision("highest"):
        erf, _ = ctx.reference.train_loss_rows(cfg, spec)(weights, *rows)
        tanh, _ = ctx.reference.train_loss_rows(
            dict(cfg, hidden_act="gelu_tanh"), spec)(weights, *rows)
    assert got == pytest.approx(float(tanh), abs=2e-5)
    assert got == pytest.approx(float(erf), abs=2e-3)
