"""BERT-family encoder model (BASELINE config 3: BERT-base sharding-2).

Capability analog of the BERT configs the reference trains through fleet
(model defs live downstream in PaddleNLP — ``BertForPretraining`` — but the
mechanics are reference in-tree: mp_layers TP shardings
``python/paddle/distributed/fleet/layers/mpu/mp_layers.py:47,333,540``,
sharding stages ``dygraph_sharding_optimizer.py:49``, flash attention
``python/paddle/nn/functional/flash_attention.py:147``).

Same TPU-native shape as ``gpt.py``: one model class; parallelism applied
afterwards as GSPMD sharding (``shard_bert``) — mesh axes decide dp/tp and
XLA's partitioner emits the Megatron collectives.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Dropout, Embedding, LayerNorm, Linear


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 512
    type_vocab_size: int = 2
    intermediate_size: int = 0  # 0 -> 4*hidden
    dropout: float = 0.0
    layer_norm_eps: float = 1e-12
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"
    # When > 0, the MLM head gathers (at most) this many masked positions
    # per sequence BEFORE the vocab projection, so the [*, vocab] GEMM and
    # loss run over ~15% of positions instead of all of them — the
    # standard BERT-pretrain optimization (the reference data pipeline
    # guarantees <= max_predictions_per_seq masked tokens per sequence;
    # positions beyond the cap are dropped, matching that contract).
    max_predictions: int = 0

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def _init(std=0.02):
    return I.Normal(mean=0.0, std=std)


def _glue_fusion() -> bool:
    from ..core import state
    return bool(state.get_flag("train_glue_fusion"))


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.word = Embedding(cfg.vocab_size, cfg.hidden_size,
                              weight_attr=_init())
        self.position = Embedding(cfg.max_seq_len, cfg.hidden_size,
                                  weight_attr=_init())
        self.token_type = Embedding(cfg.type_vocab_size, cfg.hidden_size,
                                    weight_attr=_init())
        self.ln = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.drop = Dropout(cfg.dropout)

    def forward(self, input_ids, token_type_ids=None):
        from .. import ops
        s = input_ids.shape[1]
        pos = ops.arange(0, s, dtype="int32")
        if token_type_ids is None:
            # reference BERT substitutes zeros: the learned segment-0 row
            # is always added, keeping model(ids) == model(ids, zeros)
            token_type_ids = ops.zeros_like(input_ids)
        x = (self.word(input_ids) + self.position(pos)
             + self.token_type(token_type_ids))
        return self.drop(self.ln(x))


class BertAttention(Layer):
    """Bidirectional self-attention, fused qkv (same layout as
    ``GPTAttention`` minus the causal mask)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.qkv = Linear(h, 3 * h, weight_attr=_init())
        self.proj = Linear(
            h, h, weight_attr=_init(0.02 / math.sqrt(2 * cfg.num_layers)))
        self.dropout = cfg.dropout
        self.use_flash = cfg.use_flash_attention

    def forward(self, x):
        from .. import ops
        b, s, h = x.shape
        qkv = self.qkv(x)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=False,
            dropout_p=self.dropout if self.training else 0.0,
            backend=None if self.use_flash else "xla")
        return self.proj(ops.reshape(out, [b, s, h]))


class BertLayer(Layer):
    """Post-LN encoder block (the original BERT arrangement)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.attn = BertAttention(cfg)
        self.ln1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.fc1 = Linear(cfg.hidden_size, cfg.intermediate_size,
                          weight_attr=_init())
        self.fc2 = Linear(
            cfg.intermediate_size, cfg.hidden_size,
            weight_attr=_init(0.02 / math.sqrt(2 * cfg.num_layers)))
        self.ln2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.drop = Dropout(cfg.dropout)
        self._recompute = cfg.recompute
        self._policy = (cfg.recompute_policy
                        if cfg.recompute_policy != "full" else None)

    def _inner(self, x):
        x = self.ln1(x + self.drop(self.attn(x)))
        y = self.fc2(F.gelu(self.fc1(x), approximate=True))
        return self.ln2(x + self.drop(y))

    def _inner_fused(self, x):
        """Glue-fused twin of ``_inner`` (train_glue_fusion, ISSUE 19).
        Post-LN fuses in place — each (add, norm) pair becomes one
        dispatch, no cross-block pending branch to thread."""
        _, x = F.fused_residual_norm(
            x, self.drop(self.attn(x)), self.ln1.weight, self.ln1.bias,
            epsilon=self.ln1._epsilon)
        y = self.fc2(F.gelu(self.fc1(x), approximate=True))
        _, x = F.fused_residual_norm(
            x, self.drop(y), self.ln2.weight, self.ln2.bias,
            epsilon=self.ln2._epsilon)
        return x

    def forward(self, x):
        inner = (self._inner_fused
                 if self.training and _glue_fusion() else self._inner)
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            return recompute(inner, x, policy=self._policy)
        return inner(x)


class BertModel(Layer):
    """Embeddings + encoder stack (+ [CLS] pooler)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.layers = [BertLayer(cfg) for _ in range(cfg.num_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f"layer_{i}", l)
        self.pooler = Linear(cfg.hidden_size, cfg.hidden_size,
                             weight_attr=_init())

    def forward(self, input_ids, token_type_ids=None):
        x = self.embeddings(input_ids, token_type_ids)
        for l in self.layers:
            x = l(x)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class BertForPretraining(Layer):
    """MLM (decoder tied to word embeddings) + NSP heads.
    ``forward(ids, token_type_ids, mlm_labels, nsp_labels)`` returns the
    summed mean loss; mlm positions with label -100 are ignored."""

    IGNORE = -100

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size,
                                weight_attr=_init())
        self.transform_ln = LayerNorm(cfg.hidden_size,
                                      epsilon=cfg.layer_norm_eps)
        self.nsp = Linear(cfg.hidden_size, 2, weight_attr=_init())

    def mlm_logits(self, hidden) -> Tensor:
        from .. import ops
        h = self.transform_ln(F.gelu(self.transform(hidden),
                                     approximate=True))
        return ops.matmul(h, self.bert.embeddings.word.weight,
                          transpose_y=True)

    def forward(self, input_ids, token_type_ids=None, mlm_labels=None,
                nsp_labels=None):
        from .. import ops
        hidden, pooled = self.bert(input_ids, token_type_ids)
        if mlm_labels is None:
            return self.mlm_logits(hidden)
        k = self.cfg.max_predictions
        if k and k < hidden.shape[1]:
            # gather the (<= k per sequence) masked positions first:
            # the vocab projection + loss then run over [B, k] instead
            # of [B, S]. top-k on the mask flag returns each row's
            # masked positions (ties keep ascending index order);
            # un-masked filler slots keep label IGNORE. The hidden-state
            # selection is a one-hot MATMUL, not a gather: on TPU the
            # gather's backward is a scatter-add over [B, S, H] (slower
            # where rounds 1-5 read it), while the one-hot
            # contraction's backward is another matmul on the MXU.
            flags = ops.cast(mlm_labels != self.IGNORE, "int32")
            flag_k, pos = ops.topk(flags, k, axis=-1)
            sel_labels = ops.take_along_axis(mlm_labels, pos, axis=-1)
            sel_labels = ops.where(
                flag_k > 0, sel_labels,
                ops.full_like(sel_labels, self.IGNORE))
            onehot = ops.cast(F.one_hot(pos, hidden.shape[1]),
                              hidden.dtype)                  # [B, k, S]
            sel_hidden = ops.matmul(onehot, hidden)          # [B, k, H]
            logits = self.mlm_logits(sel_hidden)
            loss = F.cross_entropy(
                ops.reshape(logits, [-1, self.cfg.vocab_size]),
                ops.reshape(sel_labels, [-1]), ignore_index=self.IGNORE)
        else:
            logits = self.mlm_logits(hidden)
            loss = F.cross_entropy(
                ops.reshape(logits, [-1, self.cfg.vocab_size]),
                ops.reshape(mlm_labels, [-1]), ignore_index=self.IGNORE)
        if nsp_labels is not None:
            loss = loss + F.cross_entropy(self.nsp(pooled), nsp_labels)
        return loss

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


class BertForSequenceClassification(Layer):
    def __init__(self, cfg: BertConfig, num_classes: int = 2):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.drop = Dropout(cfg.dropout)
        self.classifier = Linear(cfg.hidden_size, num_classes,
                                 weight_attr=_init())

    def forward(self, input_ids, token_type_ids=None, labels=None):
        _, pooled = self.bert(input_ids, token_type_ids)
        logits = self.classifier(self.drop(pooled))
        if labels is None:
            return logits
        return F.cross_entropy(logits, labels)


def shard_bert(model, mesh, dp_axis="dp", mp_axis="mp"):
    """Megatron TP shardings for the encoder (column-parallel qkv/fc1,
    row-parallel proj/fc2, vocab-parallel word embedding) — the
    ``shard_gpt`` recipe for the encoder family; dp shards the batch at
    the input (pure DP; fleet sharding stages provide ZeRO on top)."""
    from ..distributed.auto_parallel.api import (Replicate, Shard,
                                                 shard_parameter)

    names = mesh.dim_names
    if mp_axis not in names:
        return model
    mp_dim = names.index(mp_axis)

    def pl(tensor_dim):
        p = [Replicate()] * mesh.ndim
        p[mp_dim] = Shard(tensor_dim)
        return p

    bert = model.bert if hasattr(model, "bert") else model
    shard_parameter(bert.embeddings.word.weight, mesh, pl(0))
    for l in bert.layers:
        shard_parameter(l.attn.qkv.weight, mesh, pl(1))
        shard_parameter(l.attn.qkv.bias, mesh, pl(0))
        shard_parameter(l.attn.proj.weight, mesh, pl(0))
        shard_parameter(l.fc1.weight, mesh, pl(1))
        shard_parameter(l.fc1.bias, mesh, pl(0))
        shard_parameter(l.fc2.weight, mesh, pl(0))
    return model
