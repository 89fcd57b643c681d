"""The half of a decoder that ``models/deepseek_v3.py``,
``models/kimi_linear.py``, ``models/mellum.py`` and ``models/laguna.py``
share: a layer ``h = h + operator(RMSNorm(h)); h = h +
feed_forward(RMSNorm(h))`` whose operator is its family's and whose
feed-forward is

* a dense SwiGLU MLP in the leading ``first_k_dense_replace`` layers
  (``deepseek_v3``, ``kimi_linear`` and ``laguna`` lead with one;
  ``mellum`` has none, ``first_k_dense_replace`` 0);
* after them the dropless ``SparseMoEBlock``
  (``incubate/distributed/models/moe.py``; a family's ``routed_block``,
  where it has one, is what it asks of the block beyond the fields
  below: ``deepseek_v3`` and ``kimi_linear`` have none and take its
  sigmoid scores, ``mellum`` a softmax over all the experts, ``laguna``
  names the sigmoid and, as ``mellum``, a lone share's ``train_router``
  and chunk), which holds
  ``experts_held`` of the router's ``n_routed_experts`` from
  ``expert_offset`` on (one chip's share under expert parallelism),
  PLUS, where ``n_shared_experts`` is not 0 (``deepseek_v3`` 2,
  ``kimi_linear`` and ``laguna`` 1; ``mellum`` has none and builds
  none), a shared expert: one SwiGLU of ``n_shared_experts *
  moe_intermediate_size`` that every token passes.  The shared expert
  lives here and not in the block: under expert parallelism every chip
  computes it alike, and a sum over the chips' shares counts it once;

and the stack round such layers: embedding, final RMSNorm, an untied
head.  A family's config carries the fields read here under these
names (``DeepseekV3Config`` has them all).  The operator a family
gives a layer: ``deepseek_v3`` latent attention, ``kimi_linear`` KDA or
un-rotated latent attention, ``mellum`` and ``laguna``
``mellum.MellumAttention`` under the attribute ``window_attention`` or
``full_attention`` (``laguna`` with the layer's own head count and a
gate).
"""
from __future__ import annotations

import math

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Embedding, Linear, RMSNorm
from .llama import LlamaConfig, LlamaMLP


def init(std=0.02):
    return I.Normal(mean=0.0, std=std)


def out_std(cfg):
    return 0.02 / math.sqrt(2 * cfg.num_layers)


class SparseDecoderLayer(Layer):
    """One layer.  ``operator`` becomes the attribute ``name`` (so
    ``Layer.__call__`` opens a scope of that name).  ``forward`` returns
    the new hidden state; a sparse layer's routing tally is counted
    outside its recomputed region."""

    def __init__(self, cfg, index: int, name: str, operator: Layer):
        super().__init__()
        self.input_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        setattr(self, name, operator)
        self._operator = name
        self.ffn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        self.is_sparse = index >= cfg.first_k_dense_replace

        def mlp(width):
            return LlamaMLP(LlamaConfig(
                hidden_size=cfg.hidden_size, num_layers=cfg.num_layers,
                intermediate_size=width))

        if self.is_sparse:
            from ..incubate.distributed.models.moe import SparseMoEBlock
            biases = cfg.expert_bias or ()
            at = index - cfg.first_k_dense_replace
            self.routed_experts = SparseMoEBlock(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                expert_offset=cfg.expert_offset,
                experts_held=cfg.experts_held,
                routed_scaling_factor=cfg.routed_scaling_factor,
                expert_bias=biases[at] if at < len(biases) else None,
                weight_attr=init(), down_attr=init(out_std(cfg)),
                name=f"layer_{index}", norm_eps=cfg.router_norm_eps,
                **getattr(cfg, "routed_block", {}))
            if cfg.n_shared_experts:
                self.shared_expert = mlp(
                    cfg.n_shared_experts * cfg.moe_intermediate_size)
        else:
            self.mlp = mlp(cfg.intermediate_size)
        self._recompute = cfg.recompute
        self._policy = (cfg.recompute_policy
                        if cfg.recompute_policy != "full" else None)

    def _inner(self, x):
        x = x + getattr(self, self._operator)(self.input_norm(x))
        f = self.ffn_norm(x)
        if not self.is_sparse:
            return x + self.mlp(f)
        # this chip's part of the routed experts' result, and the shared
        # expert whole where the family has one
        part, *counts = self.routed_experts(f)
        if not hasattr(self, "shared_expert"):
            return (x + part, *counts)
        return (x + part + self.shared_expert(f), *counts)

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            out = recompute(self._inner, x, policy=self._policy)
        else:
            out = self._inner(x)
        if self.is_sparse:
            # outside the recomputed region, whose writes stay inside it
            self.routed_experts.count(*out[1:])
            return out[0]
        return out


class SparseDecoderModel(Layer):
    """Embedding, ``make_layer(cfg, i)`` for each layer, final norm."""

    def __init__(self, cfg, make_layer):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=init())
        self.layers = [make_layer(cfg, i) for i in range(cfg.num_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layer_{i}", layer)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class SparseDecoderForCausalLM(Layer):
    """An untied head; ``forward(ids, labels)`` is the mean next-token
    cross-entropy (labels already shifted)."""

    def __init__(self, cfg, model: SparseDecoderModel):
        super().__init__()
        self.cfg = cfg
        self.model = model
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              bias_attr=False, weight_attr=init())

    def logits(self, input_ids) -> Tensor:
        return self.lm_head(self.model(input_ids))

    def forward(self, input_ids, labels=None):
        from .. import ops
        logits = self.logits(input_ids)
        if labels is None:
            return logits
        return F.cross_entropy(
            ops.reshape(logits, [-1, self.cfg.vocab_size]),
            ops.reshape(labels, [-1]))

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def sparse_blocks(self):
        """{layer's name: its ``SparseMoEBlock``}."""
        return {f"layer_{i}": layer.routed_experts
                for i, layer in enumerate(self.model.layers)
                if layer.is_sparse}
