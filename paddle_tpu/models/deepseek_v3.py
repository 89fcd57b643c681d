"""DeepSeek-V3-style decoder (``deepseek_v3``: DeepSeek-V3, Moonshot's
Moonlight-16B-A3B).

Every layer is ``h = h + latent_attention(RMSNorm(h)); h = h +
feed_forward(RMSNorm(h))``.

* **Latent attention (MLA)**, without query compression
  (``q_lora_rank`` null): ``q = a W_q`` gives each head
  ``qk_nope_head_dim`` un-rotated and ``qk_rope_head_dim`` rotated
  dimensions; ``a W_kva`` gives a ``kv_lora_rank``-wide latent and ONE
  rotated key head all the heads share; the latent is RMS-normalised
  and projected up (``W_kvb``) to each head's un-rotated key and its
  value (``v_head_dim``).  RoPE turns the interleaved pairs
  ``(x[2i], x[2i+1])`` of the rotated slices; the shared key head is
  broadcast over the heads and joined to each head's un-rotated part.
  The scores contract over ``qk_nope + qk_rope`` (192), the values are
  ``v_head_dim`` (128) wide: one causal flash-attention call whose
  values have their own width.  No biases.
  With ``rotate`` false (``mla_use_nope`` of ``kimi_linear``) the
  ``qk_rope_head_dim`` dimensions of q and of the shared key head are
  used as they are: the same shapes, no rotation.
* The feed-forward (dense, then shared + routed experts), the stack
  and the untied head are ``models/sparse_decoder.py``'s, which
  ``models/kimi_linear.py`` shares.

Shares ``rope_angles`` with ``models/llama.py``.
Used as ``Lfm2MoeForCausalLM`` is: ``amp.decorate`` O2, ``AdamW``, one
``jit.to_static`` step, ``recompute`` per block.  It trains; the
serving engine's paged cache has no layout for a latent yet, so
``generate`` does not take it.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..core import scope as _scope
from ..core.dispatch import apply
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers import Linear, RMSNorm
from .llama import rope_angles
from .sparse_decoder import (SparseDecoderForCausalLM, SparseDecoderLayer,
                             SparseDecoderModel, init, out_std)


@dataclass
class DeepseekV3Config:
    vocab_size: int = 163840
    hidden_size: int = 2048
    num_layers: int = 27
    num_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    intermediate_size: int = 11264      # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1408   # each routed expert's
    n_shared_experts: int = 2           # one SwiGLU of n x the above
    n_routed_experts: int = 64          # the router's width
    num_experts_per_tok: int = 6
    expert_offset: int = 0              # the experts held here:
    experts_held: int = 0               # offset .. offset + held; 0 -> all
    routed_scaling_factor: float = 2.446
    router_norm_eps: float = 1e-20      # added to the selected scores' sum
    # per sparse layer, in order, the selection bias [n_routed_experts]
    # (None: zeros)
    expert_bias: tuple = field(default=None, repr=False)
    norm_eps: float = 1e-5              # rms_norm_eps
    kv_norm_eps: float = 1e-6           # the latent's norm (HF's default)
    rope_theta: float = 50000.0
    rotate: bool = True                 # False: no RoPE (mla_use_nope)
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    def __post_init__(self):
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"RoPE turns pairs: qk_rope_head_dim "
                             f"{self.qk_rope_head_dim} is odd")
        if self.experts_held == 0:
            self.experts_held = self.n_routed_experts - self.expert_offset

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _heads(q, kv, k_pe, cos, sin, cfg):
    """The kernel's operands from the three projections' results:
    ``q`` [B, S, H * (nope + rope)], ``kv`` [B, S, H * (nope + v)],
    ``k_pe`` [B, S, rope] -> q, k [B, S, H, nope + rope], v [B, S, H, v].
    ``cos`` / ``sin`` are float32 [S, rope] tables with each pair's
    angle twice (constants of the program), or None where the family
    does not rotate.  Plain jnp that XLA fuses into its neighbours."""
    h, nope, rope = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cos is not None:
        c, s = cos[None, :, None, :], sin[None, :, None, :]

    def impl(qv, kvv, pe):
        import jax.numpy as jnp

        def rot(x):
            # pairs (x[2i], x[2i+1]): the partner of an even lane is
            # its right neighbour, negated; of an odd lane its left
            x32 = x.astype(jnp.float32)
            even = jnp.arange(rope) % 2 == 0
            turned = jnp.where(even, -jnp.roll(x32, -1, axis=-1),
                               jnp.roll(x32, 1, axis=-1))
            return (x32 * c + turned * s).astype(x.dtype)

        b, n = qv.shape[:2]
        with _scope.phase("assemble"):
            qv = qv.reshape(b, n, h, nope + rope)
            kvv = kvv.reshape(b, n, h, nope + cfg.v_head_dim)
            q_pe, k_nope, v = qv[..., nope:], kvv[..., :nope], kvv[..., nope:]
        pe = pe[:, :, None, :]
        if cos is not None:
            with _scope.phase("rope"):
                q_pe, pe = rot(q_pe), rot(pe)
        with _scope.phase("assemble"):
            # the one rotated key head serves every head
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(pe, (b, n, h, rope))], axis=-1)
            q = jnp.concatenate([qv[..., :nope], q_pe], axis=-1)
        return q, k, v

    return apply("latent_heads", impl, q, kv, k_pe)


class DeepseekV3Attention(Layer):
    """Multi-head latent attention, training form: keys and values are
    expanded from the latent and attention runs over whole heads (the
    absorbed form, which attends in the latent's space, is a decode
    path's)."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.cfg = cfg
        h, heads = cfg.hidden_size, cfg.num_heads
        self.q_proj = Linear(h, heads * cfg.qk_head_dim, bias_attr=False,
                             weight_attr=init())
        # the latent and, last, the shared rotated key head
        self.kv_down = Linear(h, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                              bias_attr=False, weight_attr=init())
        self.kv_norm = RMSNorm(cfg.kv_lora_rank, epsilon=cfg.kv_norm_eps)
        # per head: the un-rotated key, then the value
        self.kv_up = Linear(
            cfg.kv_lora_rank,
            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            bias_attr=False, weight_attr=init())
        self.o_proj = Linear(heads * cfg.v_head_dim, h, bias_attr=False,
                             weight_attr=init(out_std(cfg)))

    def forward(self, x):
        import jax.numpy as jnp
        import numpy as np

        from .. import ops
        cfg = self.cfg
        b, s, _ = x.shape
        down = self.kv_down(x)
        with _scope.phase("assemble"):
            latent, k_pe = ops.split(
                down, [cfg.kv_lora_rank, cfg.qk_rope_head_dim], axis=-1)
        cos = sin = None
        if cfg.rotate:
            half = cfg.qk_rope_head_dim // 2
            cos, sin = (jnp.repeat(t[:, :half], 2, axis=-1)
                        for t in rope_angles(np.arange(s),
                                             cfg.qk_rope_head_dim,
                                             cfg.rope_theta))
        q, k, v = _heads(self.q_proj(x), self.kv_up(self.kv_norm(latent)),
                         k_pe, cos, sin, cfg)
        # the default scale is 1 / sqrt(q's width): sqrt(nope + rope)
        out = F.scaled_dot_product_attention(
            q, k, v, is_causal=True,
            backend=None if cfg.use_flash_attention else "xla")
        return self.o_proj(ops.reshape(out, [b, s, -1]))


class DeepseekV3DecoderLayer(SparseDecoderLayer):
    def __init__(self, cfg: DeepseekV3Config, index: int):
        super().__init__(cfg, index, "latent_attention",
                         DeepseekV3Attention(cfg))


class DeepseekV3Model(SparseDecoderModel):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(cfg, DeepseekV3DecoderLayer)


class DeepseekV3ForCausalLM(SparseDecoderForCausalLM):
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__(cfg, DeepseekV3Model(cfg))
