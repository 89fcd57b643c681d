"""The ``kimi_linear`` family through ``paddle_tpu/models/kimi_linear.py``."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp

# imported here, not inside build_train: a checkout whose program has
# no such family fails as this file is loaded, before any reference
# step is computed
from paddle_tpu.models.kimi_linear import (KimiLinearConfig,
                                           KimiLinearForCausalLM)
from perf import loader
from perf.reference import kimi_linear as R

from . import common
# the latent-attention layers' flash call has deepseek_v3's shapes under
# the same keys (the mean of the keys' and the values' widths)
from .deepseek_v3 import attention_shape  # noqa: F401
# the sparse block's counters are the block's, whatever the family, and
# this configuration spells the held experts as LFM2's does
from .lfm2_moe import (expert_calls, expert_counters,  # noqa: F401
                       expert_shape)

_MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
        "w2": "down_proj.weight"}
_KDA = "linear_attention."
_LEAVES = {
    "input_norm": "input_norm.weight", "ffn_norm": "ffn_norm.weight",
    **{f"kda.{x}": f"{_KDA}{x}_proj.weight" for x in "qkv"},
    **{f"kda.{x}_conv": f"{_KDA}{x}_conv" for x in "qkv"},
    **{f"kda.{x}": f"{_KDA}{x}.weight" for x in ("f_a", "f_b", "g_a", "g_b")},
    "kda.A_log": _KDA + "A_log", "kda.dt_bias": _KDA + "dt_bias",
    "kda.b": _KDA + "b_proj.weight", "kda.o_norm": _KDA + "o_norm",
    "kda.o": _KDA + "o_proj.weight",
    "attn.q": "latent_attention.q_proj.weight",
    "attn.kv_down": "latent_attention.kv_down.weight",
    "attn.kv_norm": "latent_attention.kv_norm.weight",
    "attn.kv_up": "latent_attention.kv_up.weight",
    "attn.o": "latent_attention.o_proj.weight",
    "moe.router": "routed_experts.gate.weight",
    "moe.w1": "routed_experts.w1", "moe.w3": "routed_experts.w3",
    "moe.w2": "routed_experts.w2",
    **{f"mlp.{k}": f"mlp.{v}" for k, v in _MLP.items()},
    **{f"shared.{k}": f"shared_expert.{v}" for k, v in _MLP.items()},
}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}


def program_name(leaf, layer):
    """A reference leaf's name among ``KimiLinearForCausalLM``'s
    parameters (the reference's leaves are per layer, so ``layer`` is
    always None)."""
    if leaf in _TOP:
        return _TOP[leaf]
    i, rest = re.match(r"layers\.(\d+)\.(.*)", leaf).groups()
    return f"model.layer_{i}.{_LEAVES[rest]}"


def _model(cfg, **kw):
    if cfg["tie_word_embeddings"] or cfg["q_lora_rank"] is not None \
            or not cfg["mla_use_nope"]:
        raise ValueError("this adapter builds an untied head and "
                         "uncompressed, un-rotated latent attention")
    plan = R.plan(cfg)
    ffns = [ffn for _, ffn in plan]
    if ffns != sorted(ffns):
        raise ValueError(f"dense layers lead: {ffns}")
    lin = cfg["linear_attn_config"]
    if lin["num_heads"] != cfg["num_attention_heads"]:
        raise ValueError("one head count serves both operators")
    return KimiLinearForCausalLM(KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(op for op, _ in plan),
        num_heads=cfg["num_attention_heads"],
        kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_chunk=cfg["kda_chunk"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=ffns.count("dense"),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["num_shared_experts"],
        n_routed_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_token"],
        expert_offset=cfg["expert_offset"],
        experts_held=cfg["num_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        router_norm_eps=R.ROUTER_NORM_EPS,
        expert_bias=tuple(R.expert_bias(cfg)),
        norm_eps=cfg["rms_norm_eps"], **kw))


@jax.jit
def _as_held(xs):
    """float32 values as a bfloat16 parameter holds them.  An explicit
    ``reduce_precision``: the TPU compiler takes a float32 -> bfloat16
    -> float32 pair of conversions out of a program as excess precision
    it may keep, and ``reference.common.make_weights`` rounds by such a
    pair, so on the chip the seeded weights come unrounded (PERF.md
    section 6, PR 37)."""
    return [jax.lax.reduce_precision(x.astype(jnp.float32), exponent_bits=8,
                                     mantissa_bits=7) for x in xs]


class Program(common.TrainProgram):
    """``TrainProgram`` whose parameters' change is measured from the
    initial values AS THE PROGRAM HOLDS THEM.  The base takes the
    float32 master weight minus the seeded value it is given; the
    master starts from the parameter, which holds the compute type, so
    where the seeded value was not rounded to that type the difference
    is the rounding (2 ** -9 of a leaf's value) and not the three
    AdamW steps (1e-4 each): 0.21 against 0.013 on a ``dt_bias``
    ~ N(0, 2), 0.086 against 0.0096 on a norm weight near 1."""

    def param_change_norms(self, initial):
        if self.prec["compute"] != "bfloat16":
            raise ValueError(f"compute type {self.prec['compute']}")
        low = sorted(self.low_leaves())
        held = dict(zip(low, _as_held([initial[n] for n in low])))
        return super().param_change_norms({**initial, **held})


def build_train(cfg, batch):
    prec = cfg["precision"]["train"]
    model = _model(cfg, use_flash_attention=prec["flash_attention"],
                   recompute=True,
                   recompute_policy=prec["recompute_policy"])
    return Program(model, prec, lambda m, ids, labels: m(ids, labels))


def routed_share(cfg):
    """The share of the router's slots that fall on the experts held
    here if the router spreads them evenly."""
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def kda_shape(cfg, batch):
    """A KDA call's shapes in a training step, for
    ``kernel_costs/kda_chunk``."""
    lin = cfg["linear_attn_config"]
    return dict(b=batch["rows"], h=lin["num_heads"], s=batch["seq_len"],
                dk=lin["head_dim"], dv=lin["head_dim"],
                chunk=cfg["kda_chunk"])


def train_flops_per_token(cfg, batch):
    """6 x the parameters a token multiplies with + the operators' own
    products: every matrix outside the routed experts once (the shared
    expert whole; the embedding is a lookup; the convolutions' taps,
    norm weights, ``A_log`` and ``dt_bias`` multiply elementwise and are
    left out); of the held experts a token's ``num_experts_per_token``
    slots times the share of the router's slots that fall here.  A
    latent-attention layer adds its scores and values over the half of
    the positions a causal row sees, a KDA layer the chunked delta
    rule's products, forward and backward
    (``kernel_costs/kda_chunk``).  Recomputed operations are not
    counted."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    lin = cfg["linear_attn_config"]
    width = lin["num_heads"] * lin["head_dim"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    kda = (4 * h * width                    # q, k, v, o
           + 2 * (h * lin["head_dim"] + lin["head_dim"] * width)   # f, g
           + h * lin["num_heads"])          # beta
    mla = (h * heads * qk
           + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * heads
           * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
           + heads * cfg["v_head_dim"] * h)
    cost = loader.module("kernel_costs", "kda_chunk")
    shape = kda_shape(cfg, batch)
    kda_own = (cost.fwd(**shape)[0] + cost.bwd(**shape)[0]) \
        / (batch["rows"] * batch["seq_len"])
    n = cfg["vocab_size"] * h               # the head's product
    own = 0.0
    for op, ffn in R.plan(cfg):
        if op == "kda":
            n += kda
            own += kda_own
        else:
            n += mla
            own += 3 * heads * (qk + cfg["v_head_dim"]) * batch["seq_len"]
        if ffn == "dense":
            n += 3 * h * cfg["intermediate_size"]
        else:
            n += h * cfg["published"]["num_experts"] \
                + cfg["num_shared_experts"] * expert \
                + cfg["num_experts_per_token"] * routed_share(cfg) * expert
    return 6.0 * n + own
