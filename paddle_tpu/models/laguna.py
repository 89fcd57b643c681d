"""Laguna decoder (HF ``laguna``: poolside's Laguna-XS.2, 33.4B-A3B).

Every layer is ``h = h + attention(RMSNorm(h)); h = h +
feed_forward(RMSNorm(h))`` on ``models/sparse_decoder.py``'s layer,
stack and untied head; both halves differ by layer.

* attention (``mellum.MellumAttention``, told this layer's heads and
  ``gate=True``): grouped-query, ``num_heads_per_layer[l]`` query heads
  (published: 48 on a ``full_attention`` layer, 64 on a
  ``sliding_attention`` one) over ``num_kv_heads`` key/value heads of
  ``head_dim``, no bias, no per-head norm; half-rotation RoPE on the
  FIRST ``head_dim * partial_rotary_factor`` dimensions of q and k, the
  rest passed through (``lfm2._rotate``); causal flash
  attention, on a window layer within the ``sliding_window`` keys ``i -
  window < j <= i``; then a per-head output gate, ``o_proj(concat_h(
  sigmoid(g_proj(x))_h * A_h))`` with ``g_proj`` hidden -> heads, under
  the scope ``out_gate``.
* rotary tables, one per layer type (``mellum.RopeTables``), each as
  wide as its type rotates: published, the window layers' plain over
  all 128 dimensions at theta 10,000, the full layers' YaRN over 64
  (theta 500,000, factor 64 over 4,096 positions; cos and sin times
  ``attention_factor``).
* feed-forward: a dense SwiGLU of ``intermediate_size`` in the leading
  ``first_k_dense_replace`` layers; after them one shared SwiGLU expert
  of ``n_shared_experts * moe_intermediate_size`` that every token
  passes, plus the dropless block: float32 sigmoid scores over ALL
  ``n_routed_experts``, the top ``num_experts_per_tok`` renormalised to
  sum to one, times ``routed_scaling_factor``, applied to the experts'
  output; the block holds ``experts_held`` from ``expert_offset`` on.  A
  lone share of a deployment sets ``train_router`` False and
  ``expert_slots_at_a_time`` as ``mellum`` does (``SparseMoEBlock``
  says why).

Used as ``MellumForCausalLM`` is; it trains, and ``generate`` does not
take it (head counts, and so page widths, differ by layer type).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .mellum import OPERATOR, MellumAttention, RopeTables
from .sparse_decoder import (SparseDecoderForCausalLM, SparseDecoderLayer,
                             SparseDecoderModel)


def _published_rope():
    return {
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 64,
            "original_max_position_embeddings": 4096, "beta_fast": 64,
            "beta_slow": 1, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5}}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    # per layer "sliding_attention" or "full_attention", and its query
    # heads; published: 40 layers, every fourth one full from layer 0
    layer_types: tuple = ("full_attention", "sliding_attention",
                          "sliding_attention", "sliding_attention")
    num_heads_per_layer: tuple = (48, 64, 64, 64)
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    # per layer type: rope_type "default" or "yarn", rope_theta,
    # partial_rotary_factor, and for yarn factor,
    # original_max_position_embeddings, beta_fast, beta_slow,
    # attention_factor
    rope_parameters: dict = field(default_factory=_published_rope)
    first_k_dense_replace: int = 1      # leading "dense" mlp_layer_types
    intermediate_size: int = 8192       # their SwiGLU's
    moe_intermediate_size: int = 512    # each routed expert's
    n_shared_experts: int = 1           # x moe_intermediate_size, one MLP
    n_routed_experts: int = 256         # the router's width
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    expert_offset: int = 0              # the experts held here:
    experts_held: int = 0               # offset .. offset + held; 0 -> all
    # added to the selected scores' sum before they are divided by it
    router_norm_eps: float = 1e-20
    norm_eps: float = 1e-6              # rms_norm_eps
    # a lone share of an expert-parallel deployment sets both
    # (``SparseMoEBlock``)
    train_router: bool = True
    expert_slots_at_a_time: int = None
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    expert_bias = None                  # the config has no selection bias

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.num_heads_per_layer = tuple(self.num_heads_per_layer)
        if set(self.layer_types) - set(OPERATOR):
            raise ValueError(f"layer_types {self.layer_types}")
        if len(self.num_heads_per_layer) != len(self.layer_types):
            raise ValueError(
                f"{len(self.num_heads_per_layer)} head counts for "
                f"{len(self.layer_types)} layers")
        if self.experts_held == 0:
            self.experts_held = self.n_routed_experts - self.expert_offset

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def routed_block(self):
        """What this family asks of ``SparseMoEBlock`` beyond what
        ``SparseDecoderLayer`` passes for every family."""
        return dict(scoring="sigmoid", train_router=self.train_router,
                    slots_at_a_time=self.expert_slots_at_a_time)


class LagunaModel(SparseDecoderModel):
    def __init__(self, cfg: LagunaConfig):
        tables = RopeTables(cfg)

        def layer(cfg, index):
            kind = cfg.layer_types[index]
            return SparseDecoderLayer(
                cfg, index, OPERATOR[kind],
                MellumAttention(cfg, kind, tables,
                                num_heads=cfg.num_heads_per_layer[index],
                                gate=True))

        super().__init__(cfg, layer)


class LagunaForCausalLM(SparseDecoderForCausalLM):
    def __init__(self, cfg: LagunaConfig):
        super().__init__(cfg, LagunaModel(cfg))
