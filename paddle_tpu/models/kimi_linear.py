"""Kimi-Linear decoder (HF ``kimi_linear``: Moonshot's
Kimi-Linear-48B-A3B).

Every layer is ``h = h + operator(RMSNorm(h)); h = h +
feed_forward(RMSNorm(h))``; the operator differs by layer
(``layer_types``: three ``"kda"`` to one ``"mla"`` as published) and so
does the feed-forward (dense in the leading layers, then shared +
routed experts: ``models/sparse_decoder.py``, shared with
``models/deepseek_v3.py``).

* ``"kda"``: Kimi Delta Attention, ``num_heads`` heads of
  ``kda_head_dim``.  ``q``, ``k``, ``v`` are each a projection through
  a depthwise causal convolution of ``short_conv_kernel_size`` taps and
  SiLU; the decay's logarithm is ``g = -exp(A_log) * softplus(f_b(f_a(x))
  + dt_bias)``, per CHANNEL (``A_log`` per head, ``dt_bias`` per
  channel, the projection through a ``kda_head_dim``-wide neck);
  ``beta = sigmoid(b_proj(x))`` per head; the operator is
  ``F.kda_chunk`` (``ops/pallas/kda.py``: L2-normalised q and k, the
  gated delta rule by chunks); its result passes a per-head RMSNorm
  times ``sigmoid(g_b(g_a(x)))`` and ``o_proj``.  No biases.
* ``"mla"``: ``DeepseekV3Attention`` with ``rotate`` false
  (``mla_use_nope``): the 64 "rope" dimensions of q and of the one
  shared key head are used as they are.

Used as ``DeepseekV3ForCausalLM`` is: ``amp.decorate`` O2, ``AdamW``,
one ``jit.to_static`` step, ``recompute`` per block.  It trains; the
serving engine holds no recurrent state, so ``generate`` does not take
it.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from ..core import scope as _scope
from ..core.dispatch import apply
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Linear
from .deepseek_v3 import DeepseekV3Attention
from .sparse_decoder import (SparseDecoderForCausalLM, SparseDecoderLayer,
                             SparseDecoderModel, init, out_std)


@dataclass
class KimiLinearConfig:
    vocab_size: int = 163840
    hidden_size: int = 2304
    # per layer "kda" or "mla"; published: 27 layers, every fourth and
    # the last one "mla"
    layer_types: tuple = ("kda", "kda", "kda", "mla")
    num_heads: int = 32                 # of both operators
    kda_head_dim: int = 128             # keys' and values' width, KDA
    short_conv_kernel_size: int = 4
    kda_chunk: int = None               # positions a chunk of the scan
                                        # (None: F.kda_chunk's default)
    kv_lora_rank: int = 512             # latent attention, as deepseek_v3
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64          # un-rotated here all the same
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216       # the dense layers' SwiGLU width
    moe_intermediate_size: int = 1024   # each routed expert's
    n_shared_experts: int = 1
    n_routed_experts: int = 256         # the router's width
    num_experts_per_tok: int = 8
    expert_offset: int = 0              # the experts held here:
    experts_held: int = 0               # offset .. offset + held; 0 -> all
    routed_scaling_factor: float = 2.446
    router_norm_eps: float = 1e-20
    # per sparse layer, in order, the selection bias [n_routed_experts]
    expert_bias: tuple = field(default=None, repr=False)
    norm_eps: float = 1e-5              # rms_norm_eps, every norm's
    use_flash_attention: bool = True
    recompute: bool = False
    recompute_policy: str = "full"

    rotate = False                      # mla_use_nope: the family's, no field

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if set(self.layer_types) - {"kda", "mla"}:
            raise ValueError(f"layer_types {self.layer_types}")
        if self.experts_held == 0:
            self.experts_held = self.n_routed_experts - self.expert_offset

    @property
    def num_layers(self):
        return len(self.layer_types)

    @property
    def kv_norm_eps(self):
        return self.norm_eps

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim


def _gated_delta(q, k, v, f, b, a_log, dt_bias, heads, chunk):
    """The decay gate and the operator in ONE op, so that under AMP O2,
    which hands every op its inputs in the compute type, the gate and
    its running sums are made in float32 from the projections: the
    gate here, the running sums inside ``F.kda_chunk``'s chunk kernels
    from the float32 ``g`` it is handed.  All of it stays [B, S, H * d]
    as projected (``A_log`` repeated over its head's channels): the
    four dimensions ``F.kda_chunk`` takes and returns are reshapes that
    cancel against its own."""

    def impl(q, k, v, f, b, a_log, dt_bias):
        import jax
        import jax.numpy as jnp
        shape = (*q.shape[:2], heads, -1)
        with _scope.phase("decay_gate"):
            f32 = jnp.float32
            decay = jnp.repeat(-jnp.exp(a_log.astype(f32)),
                               q.shape[-1] // heads)
            g = decay * jax.nn.softplus(f.astype(f32) + dt_bias.astype(f32))
            beta = jax.nn.sigmoid(b.astype(f32))
        with _scope.phase("kda_chunk"):
            return F.kda_chunk.raw(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                g.reshape(shape), beta, chunk=chunk).reshape(q.shape)

    return apply("gated_delta_attention", impl, q, k, v, f, b, a_log,
                 dt_bias)


def _gated_norm(o, weight, gate, heads, eps):
    """Per head ``RMSNorm(o) * weight * sigmoid(gate)``: ``o`` and
    ``gate`` [B, S, H * d], as the operator and the projection write
    them, ``weight`` [d]; float32 inside
    (``ops/pallas/kda.py`` ``gated_head_norm``: the per-head mean and
    the ``rsqrt``'s way back over the head are products with a 0/1
    indicator, so that no [B, S, H, d] is ever laid out)."""

    def impl(o, w, gate):
        from ..ops.pallas import kda
        return kda.gated_head_norm(o, w, gate, heads, eps)

    return apply("gated_rms_norm", impl, o, weight, gate)


class KimiDeltaAttention(Layer):
    """Kimi Delta Attention (no biases)."""

    def __init__(self, cfg: KimiLinearConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.kda_head_dim
        width = cfg.num_heads * d

        def proj(n_in, n_out, std=0.02):
            return Linear(n_in, n_out, bias_attr=False,
                          weight_attr=init(std))

        def taps():     # [taps, channels]
            return self.create_parameter(
                [cfg.short_conv_kernel_size, width],
                attr=init(cfg.short_conv_kernel_size ** -0.5))

        self.q_proj, self.k_proj, self.v_proj = (
            proj(h, width), proj(h, width), proj(h, width))
        self.q_conv, self.k_conv, self.v_conv = taps(), taps(), taps()
        self.f_a, self.f_b = proj(h, d), proj(d, width)
        self.A_log = self.create_parameter([cfg.num_heads], attr=init(1.0))
        self.dt_bias = self.create_parameter([width], attr=init(1.0))
        self.b_proj = proj(h, cfg.num_heads)
        self.g_a, self.g_b = proj(h, d), proj(d, width)
        self.o_norm = self.create_parameter(
            [d], default_initializer=I.Constant(1.0))
        self.o_proj = proj(width, h, out_std(cfg))

    def forward(self, x):
        cfg = self.cfg
        with _scope.phase("qkv_conv"):
            q, k, v = (F.silu(F.causal_depthwise_conv1d(proj(x), taps))
                       for proj, taps in ((self.q_proj, self.q_conv),
                                          (self.k_proj, self.k_conv),
                                          (self.v_proj, self.v_conv)))
        with _scope.phase("decay_gate"):
            f, b = self.f_b(self.f_a(x)), self.b_proj(x)
        o = _gated_delta(q, k, v, f, b, self.A_log, self.dt_bias,
                         cfg.num_heads, cfg.kda_chunk)
        with _scope.phase("out_gate_norm"):
            o = _gated_norm(o, self.o_norm, self.g_b(self.g_a(x)),
                            cfg.num_heads, cfg.norm_eps)
        return self.o_proj(o)


class KimiLinearDecoderLayer(SparseDecoderLayer):
    def __init__(self, cfg: KimiLinearConfig, index: int):
        if cfg.layer_types[index] == "kda":
            super().__init__(cfg, index, "linear_attention",
                             KimiDeltaAttention(cfg))
        else:
            super().__init__(cfg, index, "latent_attention",
                             DeepseekV3Attention(cfg))


class KimiLinearModel(SparseDecoderModel):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__(cfg, KimiLinearDecoderLayer)


class KimiLinearForCausalLM(SparseDecoderForCausalLM):
    def __init__(self, cfg: KimiLinearConfig):
        super().__init__(cfg, KimiLinearModel(cfg))
