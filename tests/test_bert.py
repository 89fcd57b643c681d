"""BERT model family (BASELINE config 3 class): forward shapes, MLM+NSP
pretraining convergence under jit, TP sharding parity, and sharding-2
(ZeRO) training on the virtual mesh."""
import numpy as np

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.models.bert import (BertConfig, BertForPretraining,
                                    BertForSequenceClassification,
                                    shard_bert)

CFG = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
           max_seq_len=32, dropout=0.0)


def _data(rng, b=4, s=16, vocab=128):
    ids = rng.integers(0, vocab, (b, s)).astype(np.int32)
    tt = (np.arange(s)[None, :] >= s // 2).astype(np.int32) * np.ones(
        (b, 1), np.int32)
    mlm = np.where(rng.random((b, s)) < 0.3, ids, -100).astype(np.int32)
    nsp = rng.integers(0, 2, (b,)).astype(np.int32)
    return ids, tt, mlm, nsp


def test_forward_shapes():
    paddle.seed(0)
    model = BertForPretraining(BertConfig(**CFG))
    rng = np.random.default_rng(0)
    ids, tt, mlm, nsp = _data(rng)
    logits = model(paddle.to_tensor(ids), paddle.to_tensor(tt))
    assert tuple(logits.shape) == (4, 16, 128)
    h, pooled = model.bert(paddle.to_tensor(ids))
    assert tuple(pooled.shape) == (4, 32)


def test_pretraining_loss_converges_under_jit():
    paddle.seed(0)
    model = BertForPretraining(BertConfig(**CFG))
    model.train()
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=model.parameters())
    rng = np.random.default_rng(1)
    ids, tt, mlm, nsp = _data(rng)

    @paddle.jit.to_static
    def step(i, t, m, n):
        loss = model(i, t, mlm_labels=m, nsp_labels=n)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    args = tuple(paddle.to_tensor(v) for v in (ids, tt, mlm, nsp))
    losses = [float(step(*args)) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.8, losses


def test_mlm_ignore_index():
    """Positions labelled -100 must not contribute to the loss."""
    paddle.seed(0)
    model = BertForPretraining(BertConfig(**CFG))
    rng = np.random.default_rng(2)
    ids, tt, mlm, _ = _data(rng)
    all_ignored = np.full_like(mlm, -100)
    l1 = model(paddle.to_tensor(ids), paddle.to_tensor(tt),
               mlm_labels=paddle.to_tensor(mlm))
    l2 = model(paddle.to_tensor(ids), paddle.to_tensor(tt),
               mlm_labels=paddle.to_tensor(all_ignored))
    assert float(l1) > 0 and abs(float(l2)) < 1e-5


def test_tp_sharding_parity():
    """shard_bert over mp=2 computes the same loss as unsharded."""
    mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["dp", "mp"])
    paddle.seed(0)
    ref = BertForPretraining(BertConfig(**CFG))
    paddle.seed(0)
    tp = BertForPretraining(BertConfig(**CFG))
    shard_bert(tp, mesh, dp_axis="dp", mp_axis="mp")
    rng = np.random.default_rng(3)
    ids, tt, mlm, nsp = _data(rng)
    args = tuple(paddle.to_tensor(v) for v in (ids, tt, mlm, nsp))
    l_ref = ref(args[0], args[1], mlm_labels=args[2], nsp_labels=args[3])
    l_tp = tp(args[0], args[1], mlm_labels=args[2], nsp_labels=args[3])
    np.testing.assert_allclose(float(l_ref), float(l_tp), rtol=1e-4)


def test_sharding2_training():
    """BASELINE config 3 shape: BERT + ZeRO sharding-2 — optimizer
    moments shard over the sharding axis and the loss still converges."""
    import paddle_tpu.distributed.fleet as fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 8,
                               "sep_degree": 1}
    hcg_prev = fleet.get_hybrid_communicate_group()
    fleet.init(is_collective=True, strategy=strategy)
    try:
        paddle.seed(0)
        model = BertForPretraining(BertConfig(**CFG))
        model.train()
        inner = paddle.optimizer.AdamW(learning_rate=3e-3,
                                       parameters=model.parameters())
        opt = fleet.DygraphShardingOptimizer(
            inner, fleet.get_hybrid_communicate_group(), stage=2)
        rng = np.random.default_rng(4)
        ids, tt, mlm, nsp = _data(rng, b=8)

        @paddle.jit.to_static
        def step(i, t, m, n):
            loss = model(i, t, mlm_labels=m, nsp_labels=n)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        args = tuple(paddle.to_tensor(v) for v in (ids, tt, mlm, nsp))
        losses = [float(step(*args)) for _ in range(8)]
        assert losses[-1] < losses[0], losses
        # adam moments really are sharded over the 8-way sharding axis
        w = model.bert.layers[0].fc1.weight
        m = inner._accumulators["moment1"][id(w)]
        shapes = {s.data.shape for s in m._read().addressable_shards}
        assert shapes == {(32 // 8, 128)}, shapes
        # and the compiled step leaves the 0-d powers replicated
        for name in ("beta1_pow", "beta2_pow"):
            v = inner._accumulators[name][id(w)]._read()
            assert v.shape == () and v.sharding.is_fully_replicated
    finally:
        fleet.set_hybrid_communicate_group(hcg_prev)


def test_sequence_classification():
    paddle.seed(0)
    model = BertForSequenceClassification(BertConfig(**CFG), num_classes=3)
    rng = np.random.default_rng(5)
    ids, tt, _, _ = _data(rng)
    logits = model(paddle.to_tensor(ids), paddle.to_tensor(tt))
    assert tuple(logits.shape) == (4, 3)
    loss = model(paddle.to_tensor(ids), paddle.to_tensor(tt),
                 labels=paddle.to_tensor(rng.integers(0, 3, (4,))
                                         .astype(np.int32)))
    assert float(loss) > 0


def test_masked_gather_mlm_head_parity():
    """cfg.max_predictions gathers the masked positions before the vocab
    projection (the reference's max_predictions_per_seq contract); with
    <= K masked per row the loss is identical to the dense head."""
    rng = np.random.default_rng(3)
    b, s, k = 3, 32, 8
    paddle.seed(0)
    dense = BertForPretraining(BertConfig(**CFG))
    paddle.seed(0)
    gathered = BertForPretraining(BertConfig(**CFG, max_predictions=k))

    ids = rng.integers(0, 128, (b, s)).astype(np.int32)
    tt = np.zeros((b, s), np.int32)
    mlm = np.full((b, s), -100, np.int32)
    for i in range(b):
        pos = rng.choice(s, size=k - 2, replace=False)
        mlm[i, pos] = rng.integers(0, 128, k - 2)
    nsp = rng.integers(0, 2, (b,)).astype(np.int32)
    args = [paddle.to_tensor(v) for v in (ids, tt, mlm, nsp)]
    np.testing.assert_allclose(float(dense(*args)), float(gathered(*args)),
                               rtol=1e-5)
    # more masked than K: extras drop, loss stays finite (the reference
    # data pipeline guarantees <= K; this is the out-of-contract guard)
    over = np.where(rng.random((b, s)) < 0.9, ids, -100).astype(np.int32)
    lv = float(gathered(args[0], args[1], paddle.to_tensor(over), args[3]))
    assert np.isfinite(lv)
