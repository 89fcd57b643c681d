"""Mellum decoder (HF ``mellum``: JetBrains' Mellum2-12B-A2.5B).

Every layer is ``h = h + attention(RMSNorm(h)); h = h +
routed_experts(RMSNorm(h))``: grouped-query attention whose kind
differs by layer (``layer_types``: three ``"sliding_attention"`` to one
``"full_attention"`` as published), and in EVERY layer the dropless
block of ``models/sparse_decoder.py`` with no shared expert beside it
and no dense layer before it.

* attention: ``q`` (``num_heads`` heads of ``head_dim``), ``k``, ``v``
  (``num_kv_heads``), no bias, no per-head norm; half-rotation RoPE
  (``lfm2._rotate``); causal flash attention, and on a
  ``sliding_attention`` layer the ``sliding_window`` bound: query ``i``
  sees the keys ``i - window < j <= i`` (``F.scaled_dot_product_attention``'s
  ``window``: the flash kernels visit no tile behind it).
* rotary tables, one per layer type (``rope_parameters``): the window
  layers' plain at ``rope_theta``, the full layers' YaRN
  (``llama.yarn_inv_freq``, cos and sin times ``attention_factor``).
  They are made on the host once per layer type and length
  (``RopeTables``), and a layer picks its own.
* experts: a float32 softmax over ALL the router's logits, the top
  ``num_experts_per_tok`` of it renormalised to sum to one (no bias, no
  scaling factor); the block holds ``experts_held`` of them from
  ``expert_offset`` on.  A lone share of a deployment (fewer held than
  the router has) sets ``train_router`` False and, where the block's
  chunk would end at its expected load, ``expert_slots_at_a_time``
  (``SparseMoEBlock`` says why).

Used as ``DeepseekV3ForCausalLM`` is: ``amp.decorate`` O2, ``AdamW``,
one ``jit.to_static`` step, ``recompute`` per block.  It trains; the
serving engine's page tables are one per model, not per layer type, and
its paged kernel has no window bound, so ``generate`` does not take it.

``MellumAttention`` and ``RopeTables`` are built by two families:
``mellum`` (here: ``cfg.num_heads`` in every layer, the whole head
rotated, no gate) and ``laguna`` (``models/laguna.py``: the layer's own
head count, a layer type's ``partial_rotary_factor`` of the head
rotated, ``gate=True``).
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..core import scope as _scope
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers import Linear
from .lfm2 import _rotate
from .llama import rope_angles, yarn_inv_freq
from .sparse_decoder import (SparseDecoderForCausalLM, SparseDecoderLayer,
                             SparseDecoderModel, init, out_std)

# the attribute a layer's attention is held under, so the scope
# ``Layer.__call__`` opens for it, by the layer's published type
OPERATOR = {"sliding_attention": "window_attention",
            "full_attention": "full_attention"}


def _published_rope():
    return {
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}}


@dataclass
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    # per layer "sliding_attention" or "full_attention"; published: 28
    # layers, every fourth one full
    layer_types: tuple = ("sliding_attention", "sliding_attention",
                          "sliding_attention", "full_attention")
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    # per layer type: rope_type "default" or "yarn", rope_theta, and
    # for yarn factor, original_max_position_embeddings, beta_fast,
    # beta_slow, attention_factor
    rope_parameters: dict = field(default_factory=_published_rope)
    moe_intermediate_size: int = 896    # each routed expert's
    n_routed_experts: int = 64          # the router's width
    num_experts_per_tok: int = 8
    expert_offset: int = 0              # the experts held here:
    experts_held: int = 0               # offset .. offset + held; 0 -> all
    norm_eps: float = 1e-6              # rms_norm_eps
    # a lone share of an expert-parallel deployment sets both
    # (``SparseMoEBlock``): False, and a chunk that does not end at the
    # even spread's load (None: the block's own)
    train_router: bool = True
    expert_slots_at_a_time: int = None
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    # what ``sparse_decoder`` reads and this family fixes: every layer
    # sparse, no shared expert, a softmax router (``routed_block``)
    # whose selected scores are divided by their plain sum, no
    # selection bias
    first_k_dense_replace = 0
    n_shared_experts = 0
    routed_scaling_factor = 1.0
    router_norm_eps = 0.0
    expert_bias = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if set(self.layer_types) - set(OPERATOR):
            raise ValueError(f"layer_types {self.layer_types}")
        if self.head_dim % 2:
            raise ValueError(f"RoPE turns halves: head_dim {self.head_dim}")
        if self.experts_held == 0:
            self.experts_held = self.n_routed_experts - self.expert_offset

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def routed_block(self):
        """What this family asks of ``SparseMoEBlock`` beyond what
        ``SparseDecoderLayer`` passes for every family."""
        return dict(scoring="softmax", train_router=self.train_router,
                    slots_at_a_time=self.expert_slots_at_a_time)


class RopeTables:
    """(cos, sin) float32 [positions, width] by layer type: made on
    the host the first time a length is asked for and kept, so a stack
    makes each of its two tables once, whatever its depth.  A table is
    as wide as the part of a head its layer type rotates
    (``width(kind)``: ``head_dim`` times the group's
    ``partial_rotary_factor``, 1 where it has none), and YaRN's
    frequencies are blended over that width."""

    def __init__(self, cfg):
        self.cfg, self._made = cfg, {}

    def width(self, kind):
        d = self.cfg.head_dim
        r = int(d * self.cfg.rope_parameters[kind].get(
            "partial_rotary_factor", 1))
        if r % 2 or not 0 < r <= d:
            raise ValueError(f"RoPE turns halves of {r} of {d} dimensions")
        return r

    def get(self, kind, positions):
        if (kind, positions) not in self._made:
            import numpy as np
            d, p = self.width(kind), self.cfg.rope_parameters[kind]
            if p["rope_type"] == "default":
                extra = {}
            elif p["rope_type"] == "yarn":
                extra = dict(
                    inv_freq=yarn_inv_freq(
                        d, p["rope_theta"], p["factor"],
                        p["original_max_position_embeddings"],
                        p["beta_fast"], p["beta_slow"]),
                    scale=p["attention_factor"])
            else:
                raise ValueError(f"rope_type {p['rope_type']!r}")
            import jax
            # concrete arrays even where a program is being traced: a
            # table is kept across traces
            with jax.ensure_compile_time_eval():
                self._made[kind, positions] = rope_angles(
                    np.arange(positions), d, p["rope_theta"], **extra)
        return self._made[kind, positions]


class MellumAttention(Layer):
    """Grouped-query attention of one layer type (no biases, no
    per-head norm): ``num_heads`` query heads (``cfg.num_heads`` unless
    given: a family whose head count differs by layer gives the
    layer's), the layer type's table wide of each head rotated, and
    with ``gate`` a per-head sigmoid gate on the attention's output,
    ``o_proj(concat_h(sigmoid(g_proj(x))_h * A_h))``."""

    def __init__(self, cfg, kind: str, tables: RopeTables, num_heads=None,
                 gate=False):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        heads = cfg.num_heads if num_heads is None else num_heads
        if heads % cfg.num_kv_heads:
            raise ValueError(f"{heads} query heads over {cfg.num_kv_heads}")
        self.num_heads, self.num_kv_heads = heads, cfg.num_kv_heads
        self.head_dim, self.kind, self._tables = d, kind, tables
        self.window = (cfg.sliding_window if kind == "sliding_attention"
                       else None)
        self.use_flash = cfg.use_flash_attention
        self.q_proj = Linear(h, heads * d, bias_attr=False,
                             weight_attr=init())
        self.k_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=init())
        self.v_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=init())
        if gate:
            self.g_proj = Linear(h, heads, bias_attr=False,
                                 weight_attr=init())
        self.o_proj = Linear(heads * d, h, bias_attr=False,
                             weight_attr=init(out_std(cfg)))

    def forward(self, x):
        from .. import ops
        b, s, _ = x.shape
        with _scope.phase("qkv"):
            q = ops.reshape(self.q_proj(x),
                            [b, s, self.num_heads, self.head_dim])
            k = ops.reshape(self.k_proj(x),
                            [b, s, self.num_kv_heads, self.head_dim])
            v = ops.reshape(self.v_proj(x),
                            [b, s, self.num_kv_heads, self.head_dim])
        with _scope.phase("rope"):
            q, k = _rotate(q, k, *self._tables.get(self.kind, s))
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True, window=self.window,
            backend=None if self.use_flash else "xla")
        if hasattr(self, "g_proj"):
            with _scope.phase("out_gate"):
                out = out * ops.unsqueeze(F.sigmoid(self.g_proj(x)), -1)
        return self.o_proj(ops.reshape(out, [b, s, -1]))


class MellumModel(SparseDecoderModel):
    def __init__(self, cfg: MellumConfig):
        tables = RopeTables(cfg)

        def layer(cfg, index):
            kind = cfg.layer_types[index]
            return SparseDecoderLayer(cfg, index, OPERATOR[kind],
                                      MellumAttention(cfg, kind, tables))

        super().__init__(cfg, layer)


class MellumForCausalLM(SparseDecoderForCausalLM):
    def __init__(self, cfg: MellumConfig):
        super().__init__(cfg, MellumModel(cfg))
