"""The ``deepseek_v3`` family through ``paddle_tpu/models/deepseek_v3.py``."""
from __future__ import annotations

import re

# imported here, not inside build_train: a checkout whose program has
# no such family fails as this file is loaded, before any reference
# step is computed
from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                           DeepseekV3ForCausalLM)
from perf.reference import deepseek_v3 as R

from . import common
# the sparse block's counters are the block's, whatever the family
from .lfm2_moe import expert_calls, expert_counters  # noqa: F401

_MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
        "w2": "down_proj.weight"}
_LEAVES = {
    "input_norm": "input_norm.weight", "ffn_norm": "ffn_norm.weight",
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
    """A reference leaf's name among ``DeepseekV3ForCausalLM``'s
    parameters (the reference's leaves are per layer, so ``layer`` is
    always None)."""
    if leaf in _TOP:
        return _TOP[leaf]
    i, rest = re.match(r"layers\.(\d+)\.(.*)", leaf).groups()
    return f"model.layer_{i}.{_LEAVES[rest]}"


def _model(cfg, **kw):
    if cfg["tie_word_embeddings"] or cfg["q_lora_rank"] is not None:
        raise ValueError("this adapter builds an untied head and "
                         "uncompressed queries")
    plan = R.plan(cfg)
    if plan != sorted(plan):
        raise ValueError(f"dense layers lead: {plan}")
    return DeepseekV3ForCausalLM(DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=len(plan), num_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        first_k_dense_replace=plan.count("dense"),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        n_routed_experts=cfg["published"]["n_routed_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_offset=cfg["expert_offset"],
        experts_held=cfg["n_routed_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        router_norm_eps=R.ROUTER_NORM_EPS,
        expert_bias=tuple(R.expert_bias(cfg)),
        norm_eps=cfg["rms_norm_eps"], kv_norm_eps=cfg["kv_norm_eps"],
        rope_theta=cfg["rope_theta"], **kw))


def build_train(cfg, batch):
    prec = cfg["precision"]["train"]
    model = _model(cfg, use_flash_attention=prec["flash_attention"],
                   recompute=True,
                   recompute_policy=prec["recompute_policy"])
    return common.TrainProgram(
        model, prec, lambda m, ids, labels: m(ids, labels))


def routed_share(cfg):
    """The share of the router's slots that fall on the experts held
    here if the router spreads them evenly."""
    return cfg["n_routed_experts"] / cfg["published"]["n_routed_experts"]


def expert_shape(cfg):
    """The held experts' products' shapes, for
    ``kernel_costs/expert_mlp``: the experts held here, the hidden
    width and an expert's width."""
    return dict(held=cfg["n_routed_experts"], h=cfg["hidden_size"],
                i=cfg["moe_intermediate_size"])


def train_flops_per_token(cfg, batch):
    """6 x the parameters a token multiplies with + attention's scores
    and values: every matrix outside the routed experts once (the
    shared expert whole; the embedding is a lookup); of the held
    experts a token's ``num_experts_per_tok`` slots times the share of
    the router's slots that fall here.  Attention: the score product
    over ``qk_nope + qk_rope`` and the value product over ``v_head_dim``,
    forward and twice that backward, over the half of the positions a
    causal row sees on average.  Norm weights multiply elementwise and
    are left out; recomputed operations are not counted."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    operator = (h * heads * qk
                + h * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                + cfg["kv_lora_rank"] * heads
                * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
                + heads * cfg["v_head_dim"] * h)
    n = cfg["vocab_size"] * h               # the head's product
    attn = 0
    for ffn in R.plan(cfg):
        n += operator
        attn += 3 * heads * (qk + cfg["v_head_dim"]) * batch["seq_len"]
        if ffn == "dense":
            n += 3 * h * cfg["intermediate_size"]
        else:
            n += h * cfg["published"]["n_routed_experts"] \
                + cfg["n_shared_experts"] * expert \
                + cfg["num_experts_per_tok"] * routed_share(cfg) * expert
    return 6.0 * n + attn


def attention_shape(cfg, batch):
    """The flash-attention call's shapes in a training step, for
    ``kernel_costs/flash_attention``, which takes ONE width: the mean
    of the keys' (``qk_nope + qk_rope``) and the values' (``v_head_dim``)
    gives its formulas the products and the bytes of the two widths
    exactly (two products over each width forward, four backward; q, k,
    dq, dk at the keys' width and v, o, do, dv at the values')."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return dict(b=batch["rows"], h=cfg["num_attention_heads"],
                sq=batch["seq_len"], sk=batch["seq_len"],
                d=(qk + cfg["v_head_dim"]) / 2, causal=True)
