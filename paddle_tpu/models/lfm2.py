"""LFM2-MoE decoder (``lfm2_moe``: LiquidAI's LFM2-8B-A1B / LFM2-24B-A2B).

The first family here whose blocks are not all alike.  Every layer is
``h = h + operator(RMSNorm(h)); h = h + feed_forward(RMSNorm(h))``, and
both halves differ from layer to layer:

* the operator is a **gated short convolution** (``conv``: one input
  projection split into gates B, C and a value X; a depthwise causal
  convolution of ``conv_L_cache`` taps over ``B * X``; the result gated
  by C and projected out) or **grouped-query attention**
  (``full_attention``: RMSNorm over each q and k head, half-rotation
  RoPE, causal flash attention), as ``layer_types`` says;
* the feed-forward is a dense SwiGLU MLP in the leading
  ``num_dense_layers`` and after them the dropless sigmoid-routed
  ``SparseMoEBlock`` (``incubate/distributed/models/moe.py``), which
  holds ``experts_held`` of the router's ``num_experts`` from
  ``expert_offset`` on: one chip's share under expert parallelism.

With ``models/llama.py`` it shares the rope tables (``rope_angles``),
the SwiGLU MLP and the attention call.  Used as ``GPTForCausalLM`` is:
``amp.decorate`` O2, ``AdamW``, one ``jit.to_static`` step, ``recompute``
per block.  It trains; the serving engine's paged cache has no place
for a conv layer's state yet, so ``generate`` does not take it.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from ..core import scope as _scope
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Embedding, Linear, RMSNorm
from .llama import LlamaConfig, LlamaMLP, rope_angles


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple = ("conv", "conv", "full_attention", "conv")
    num_dense_layers: int = 2
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 11776      # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1536   # each expert's
    num_experts: int = 64               # the router's width
    num_experts_per_tok: int = 4
    expert_offset: int = 0              # the experts held here:
    experts_held: int = 0               # offset .. offset + held; 0 -> all
    routed_scaling_factor: float = 1.0
    # per sparse layer, in order, the selection bias [num_experts]
    # (None: zeros)
    expert_bias: tuple = field(default=None, repr=False)
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types {self.layer_types}")
        if self.experts_held == 0:
            self.experts_held = self.num_experts - self.expert_offset

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def _init(std=0.02):
    return I.Normal(mean=0.0, std=std)


def _out_std(cfg):
    return 0.02 / math.sqrt(2 * cfg.num_layers)


class Lfm2ShortConv(Layer):
    """The gated short-convolution operator (no biases)."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        h = cfg.hidden_size
        self.in_proj = Linear(h, 3 * h, bias_attr=False, weight_attr=_init())
        # [taps, channels]: tap j multiplies position t - (taps - 1) + j
        self.conv_weight = self.create_parameter(
            [cfg.conv_L_cache, h],
            attr=_init(1.0 / math.sqrt(cfg.conv_L_cache)))
        self.out_proj = Linear(h, h, bias_attr=False,
                               weight_attr=_init(_out_std(cfg)))

    def forward(self, x):
        from .. import ops
        b, c, v = ops.split(self.in_proj(x), 3, axis=-1)
        with _scope.phase("short_conv"):
            y = c * F.causal_depthwise_conv1d(b * v, self.conv_weight)
        return self.out_proj(y)


def _rotate(q, k, cos, sin):
    """Half-rotation RoPE on [B, S, H, D] heads from float32 [S, D]
    tables (constants of the program, not operands AMP would cast).
    Tables narrower than the head, [S, r], turn each head's first
    ``r`` dimensions (the halves within THOSE) and pass the rest
    through: HF's ``q_rot, q_pass`` (``laguna``'s full layers).
    In a ``to_static`` program captured on a TPU a head of 128 goes
    through the position-tiled kernel ``ops/pallas/rope.py``
    ``half_turn``, one pass over q and one over k forward and backward
    (XLA makes float32 halves 64 lanes wide of the split and the
    concatenation, and a recompute policy that keeps products and
    kernels runs them again).  Any other width, any other backend, and
    per-op dispatch (a program's eager first call: every call of a
    kernel there is traced and lowered anew, 0.2 s each and 5.6 s of a
    cell's set-up on the v5e, where these few elementwise programs are
    made once) are the plain jnp form below."""
    import jax
    from ..ops.pallas import rope
    if (q.shape[-1] == rope.HEAD and jax.default_backend() == "tpu"
            and _scope.current() is not None):
        return apply("rope", lambda qv, kv: (rope.half_turn(qv, cos, sin),
                                             rope.half_turn(kv, cos, sin)),
                     q, k)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    r = cos.shape[-1]

    def impl(qv, kv):
        import jax.numpy as jnp

        def rot(x):
            whole = r == x.shape[-1]
            x32 = (x if whole else x[..., :r]).astype(jnp.float32)
            x1, x2 = jnp.split(x32, 2, axis=-1)
            turned = jnp.concatenate([-x2, x1], axis=-1)
            out = (x32 * c + turned * s).astype(x.dtype)
            return out if whole else jnp.concatenate(
                [out, x[..., r:]], axis=-1)

        return rot(qv), rot(kv)

    return apply("rope", impl, q, k)


class Lfm2Attention(Layer):
    """Grouped-query attention with an RMSNorm over each q and k head
    before the rotation."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        self.num_heads, self.num_kv_heads = cfg.num_heads, cfg.num_kv_heads
        self.head_dim, self.rope_theta = d, cfg.rope_theta
        self.use_flash = cfg.use_flash_attention
        self.q_proj = Linear(h, cfg.num_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.k_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.v_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.out_proj = Linear(cfg.num_heads * d, h, bias_attr=False,
                               weight_attr=_init(_out_std(cfg)))
        self.q_layernorm = RMSNorm(d, epsilon=cfg.norm_eps)
        self.k_layernorm = RMSNorm(d, epsilon=cfg.norm_eps)

    def forward(self, x):
        import numpy as np

        from .. import ops
        b, s, _ = x.shape
        q = ops.reshape(self.q_proj(x), [b, s, self.num_heads, self.head_dim])
        k = ops.reshape(self.k_proj(x),
                        [b, s, self.num_kv_heads, self.head_dim])
        v = ops.reshape(self.v_proj(x),
                        [b, s, self.num_kv_heads, self.head_dim])
        cos, sin = rope_angles(np.arange(s), self.head_dim, self.rope_theta)
        q, k = _rotate(self.q_layernorm(q), self.k_layernorm(k), cos, sin)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            backend=None if self.use_flash else "xla")
        return self.out_proj(ops.reshape(out, [b, s, -1]))


class Lfm2DecoderLayer(Layer):
    """One layer: its operator and its feed-forward are chosen by its
    place in the stack.  ``forward`` returns the new hidden state and,
    from a sparse layer, the call's routing tally (else None)."""

    def __init__(self, cfg: Lfm2MoeConfig, index: int):
        super().__init__()
        self.operator_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        self.is_attention = cfg.layer_types[index] == "full_attention"
        if self.is_attention:
            self.self_attn = Lfm2Attention(cfg)
        else:
            self.conv = Lfm2ShortConv(cfg)
        self.ffn_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)
        self.is_sparse = index >= cfg.num_dense_layers
        if self.is_sparse:
            from ..incubate.distributed.models.moe import SparseMoEBlock
            biases = cfg.expert_bias or ()
            at = index - cfg.num_dense_layers
            self.feed_forward = SparseMoEBlock(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts, cfg.num_experts_per_tok,
                expert_offset=cfg.expert_offset,
                experts_held=cfg.experts_held,
                routed_scaling_factor=cfg.routed_scaling_factor,
                expert_bias=biases[at] if at < len(biases) else None,
                weight_attr=_init(), down_attr=_init(_out_std(cfg)),
                name=f"layer_{index}")
        else:
            self.feed_forward = LlamaMLP(LlamaConfig(
                hidden_size=cfg.hidden_size, num_layers=cfg.num_layers,
                intermediate_size=cfg.intermediate_size))
        self._recompute = cfg.recompute
        self._policy = (cfg.recompute_policy
                        if cfg.recompute_policy != "full" else None)

    def _inner(self, x):
        a = self.operator_norm(x)
        x = x + (self.self_attn(a) if self.is_attention else self.conv(a))
        f = self.feed_forward(self.ffn_norm(x))
        if self.is_sparse:
            return (x + f[0], *f[1:])
        return x + f

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            out = recompute(self._inner, x, policy=self._policy)
        else:
            out = self._inner(x)
        if self.is_sparse:
            # outside the recomputed region, whose writes stay inside it
            self.feed_forward.count(*out[1:])
            return out[0]
        return out


class Lfm2MoeModel(Layer):
    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=_init())
        self.layers = [Lfm2DecoderLayer(cfg, i)
                       for i in range(cfg.num_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layer_{i}", layer)
        self.embedding_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.embedding_norm(x)


class Lfm2MoeForCausalLM(Layer):
    """Head tied to the embedding; ``forward(ids, labels)`` is the mean
    next-token cross-entropy (labels already shifted)."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        self.lfm2 = Lfm2MoeModel(cfg)

    def logits(self, input_ids) -> Tensor:
        from .. import ops
        h = self.lfm2(input_ids)
        with _scope.phase("lm_head"):
            return ops.matmul(h, self.lfm2.embed_tokens.weight,
                              transpose_y=True)

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
        return {f"layer_{i}": layer.feed_forward
                for i, layer in enumerate(self.lfm2.layers)
                if layer.is_sparse}
