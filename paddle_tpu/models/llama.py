"""LLaMA-family decoder model (BASELINE config 4: "GPT-1.3B/LLaMA-7B
TP+PP+recompute+flash-attn").

Capability analog of the LLaMA configs the reference trains through fleet
(model defs live in PaddleNLP; the mechanics are in-tree: rms_norm + rope
fused kernels ``paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu``,
``rms_norm_kernel``, flash attention with GQA
``python/paddle/nn/functional/flash_attention.py:147``, mp_layers TP).

Same TPU-native shape as ``gpt.py``: one model class, parallelism applied
afterwards (``shard_llama``); the compute path rides the Pallas tier
(flash attention with grouped-query heads, fused rms_norm, rope).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Embedding, Linear, RMSNorm


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0          # 0 -> num_heads (MHA); < heads = GQA
    max_seq_len: int = 2048
    intermediate_size: int = 0     # 0 -> the LLaMA 8/3 * hidden rule
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size == 0:
            # LLaMA sizing: 2/3 * 4h rounded up to a multiple of 256
            raw = int(8 * self.hidden_size / 3)
            self.intermediate_size = 256 * ((raw + 255) // 256)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def _init(std=0.02):
    return I.Normal(mean=0.0, std=std)


def _glue_fusion() -> bool:
    from ..core import state
    return bool(state.get_flag("train_glue_fusion"))


def yarn_inv_freq(d, theta, factor, original_max_position_embeddings,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's blended rotary frequencies, float64 [d // 2], as HF's
    ``_compute_yarn_parameters``: pair ``i`` turns at the plain
    ``theta ** (-2i / d)`` where it makes more than ``beta_fast`` turns
    over the original context (extrapolation), at that divided by
    ``factor`` where it makes fewer than ``beta_slow`` (interpolation),
    and at a linear blend between."""
    import math

    import numpy as np
    extra = 1.0 / theta ** (np.arange(0, d // 2) * 2.0 / d)

    def pair_of(turns):     # the pair that makes ``turns`` turns
        return d * math.log(original_max_position_embeddings
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def rope_angles(positions, d, theta, inv_freq=None, scale=1.0):
    """Half-rotation rope tables: (cos, sin) [..., d] for ``positions``
    (numpy or traced jnp values). SINGLE home of the LLaMA rope
    convention — the training path (_rope_tables) and the KV-cache decode
    path (generation.rope_at) both read it.  ``inv_freq`` [d // 2]
    replaces the plain ``theta ** (-2i / d)`` (``yarn_inv_freq``) and
    ``scale`` multiplies cos and sin alike (YaRN's ``attention_factor``).

    Concrete positions compute in float64 (f32 loses ~1e-4 rad at
    position 2048 — enough to drift checkpoints); traced positions (the
    decode path) necessarily stay f32, still within the cache/full parity
    tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if not isinstance(positions, jax.core.Tracer):
        inv = (1.0 / theta ** (np.arange(0, d // 2) * 2.0 / d)
               if inv_freq is None else np.asarray(inv_freq, np.float64))
        ang = np.asarray(positions, np.float64)[..., None] * inv
        ang = np.concatenate([ang, ang], axis=-1)
        return (jnp.asarray(np.cos(ang) * scale, jnp.float32),
                jnp.asarray(np.sin(ang) * scale, jnp.float32))
    inv = (1.0 / theta ** (jnp.arange(0, d // 2) * 2.0 / d)
           if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    ang = positions[..., None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    if scale == 1.0:
        return jnp.cos(ang), jnp.sin(ang)
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


class LlamaAttention(Layer):
    """Rope + grouped-query flash attention. KV projections emit
    ``num_kv_heads`` heads; the Pallas kernel maps q-head -> kv-head
    (the reference's GQA flash_attn path)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, d = cfg.hidden_size, cfg.head_dim
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = d
        self.rope_theta = cfg.rope_theta
        self.q_proj = Linear(h, cfg.num_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.k_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.v_proj = Linear(h, cfg.num_kv_heads * d, bias_attr=False,
                             weight_attr=_init())
        self.o_proj = Linear(cfg.num_heads * d, h, bias_attr=False,
                             weight_attr=_init(0.02 / math.sqrt(
                                 2 * cfg.num_layers)))

    def _rope_tables(self, s):
        """cos/sin [s, head_dim] for this config's rope_theta."""
        import numpy as np
        cos, sin = rope_angles(np.arange(s), self.head_dim,
                               self.rope_theta)
        return Tensor(cos), Tensor(sin)

    def forward(self, x):
        from .. import ops
        from ..incubate.nn.functional import \
            fused_rotary_position_embedding as rope
        b, s, h = x.shape
        q = ops.reshape(self.q_proj(x), [b, s, self.num_heads,
                                         self.head_dim])
        k = ops.reshape(self.k_proj(x), [b, s, self.num_kv_heads,
                                         self.head_dim])
        v = ops.reshape(self.v_proj(x), [b, s, self.num_kv_heads,
                                         self.head_dim])
        # half-rotation convention (LLaMA/HF); explicit tables carry
        # this config's rope_theta (the kernel default is base 10000)
        cos, sin = self._rope_tables(s)
        q, k, _ = rope(q, k, sin=sin, cos=cos,
                       use_neox_rotary_style=False)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(ops.reshape(out, [b, s, -1]))


class LlamaMLP(Layer):
    """SwiGLU FFN (gate/up/down), the reference's fused swiglu path."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.gate_proj = Linear(cfg.hidden_size, cfg.intermediate_size,
                                bias_attr=False, weight_attr=_init())
        self.up_proj = Linear(cfg.hidden_size, cfg.intermediate_size,
                              bias_attr=False, weight_attr=_init())
        self.down_proj = Linear(
            cfg.intermediate_size, cfg.hidden_size, bias_attr=False,
            weight_attr=_init(0.02 / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        from ..incubate.nn.functional import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.attn = LlamaAttention(cfg)
        self.post_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mlp = LlamaMLP(cfg)
        self._recompute = cfg.recompute
        self._policy = (cfg.recompute_policy
                        if cfg.recompute_policy != "full" else None)

    def _inner(self, x):
        x = x + self.attn(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._inner, x, policy=self._policy)
        return self._inner(x)

    def _inner_fused(self, x, pending=None):
        """Glue-fused twin of ``_inner`` (train_glue_fusion, ISSUE 19):
        same pending-branch threading as GPTBlock._inner_fused — the
        previous layer's un-added MLP branch fuses with this layer's
        input_norm, the attention branch with post_norm; the RMS pair
        (add, norm) runs as one fused dispatch each."""
        if pending is None:
            h1 = self.input_norm(x)
        else:
            x, h1 = F.fused_residual_norm(
                x, pending, self.input_norm.weight, norm="rms",
                epsilon=self.input_norm._epsilon)
        a = self.attn(h1)
        x, h2 = F.fused_residual_norm(
            x, a, self.post_norm.weight, norm="rms",
            epsilon=self.post_norm._epsilon)
        return x, self.mlp(h2)

    def forward_fused(self, x, pending=None):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            if pending is None:
                return recompute(self._inner_fused, x,
                                 policy=self._policy)
            return recompute(self._inner_fused, x, pending,
                             policy=self._policy)
        return self._inner_fused(x, pending)


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(cfg.vocab_size, cfg.hidden_size,
                                      weight_attr=_init())
        self.layers = [LlamaDecoderLayer(cfg) for _ in range(cfg.num_layers)]
        for i, l in enumerate(self.layers):
            self.add_sublayer(f"layer_{i}", l)
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.training and self.layers and _glue_fusion():
            pending = None
            for l in self.layers:
                x, pending = l.forward_fused(x, pending)
            _, h = F.fused_residual_norm(
                x, pending, self.norm.weight, norm="rms",
                epsilon=self.norm._epsilon)
            return h
        for l in self.layers:
            x = l(x)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    """LM head on top; ``forward(ids, labels)`` = mean next-token CE."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.llama = LlamaModel(cfg)
        if cfg.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  bias_attr=False, weight_attr=_init())

    def logits(self, input_ids) -> Tensor:
        from .. import ops
        h = self.llama(input_ids)
        if self.lm_head is not None:
            return self.lm_head(h)
        return ops.matmul(h, self.llama.embed_tokens.weight,
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

    def flops_per_token(self, seq_len: int) -> float:
        n = self.num_params()
        attn = 12 * self.cfg.num_layers * self.cfg.hidden_size * seq_len
        return 6.0 * n + attn


def shard_llama(model: LlamaForCausalLM, mesh, dp_axis="dp", mp_axis="mp"):
    """Megatron TP recipe: column-parallel q/k/v/gate/up (output dim over
    mp), row-parallel o/down (input dim over mp), vocab-parallel
    embedding + head. KV heads shard over mp too — valid while
    ``num_kv_heads % mp == 0`` (the reference's GQA TP constraint)."""
    from ..distributed.auto_parallel.api import (Replicate, Shard,
                                                 shard_parameter)

    names = mesh.dim_names
    if mp_axis not in names:
        return model
    mp = dict(zip(getattr(mesh, "jmesh", mesh).axis_names,
                  getattr(mesh, "jmesh", mesh).devices.shape))[mp_axis]
    if model.cfg.num_kv_heads % mp:
        raise ValueError(f"num_kv_heads {model.cfg.num_kv_heads} not "
                         f"divisible by mp degree {mp}")
    mp_dim = names.index(mp_axis)

    def pl(tensor_dim):
        p = [Replicate()] * mesh.ndim
        p[mp_dim] = Shard(tensor_dim)
        return p

    shard_parameter(model.llama.embed_tokens.weight, mesh, pl(0))
    for l in model.llama.layers:
        shard_parameter(l.attn.q_proj.weight, mesh, pl(1))
        shard_parameter(l.attn.k_proj.weight, mesh, pl(1))
        shard_parameter(l.attn.v_proj.weight, mesh, pl(1))
        shard_parameter(l.attn.o_proj.weight, mesh, pl(0))
        shard_parameter(l.mlp.gate_proj.weight, mesh, pl(1))
        shard_parameter(l.mlp.up_proj.weight, mesh, pl(1))
        shard_parameter(l.mlp.down_proj.weight, mesh, pl(0))
    if model.lm_head is not None:
        shard_parameter(model.lm_head.weight, mesh, pl(1))
    return model
