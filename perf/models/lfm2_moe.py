"""The ``lfm2_moe`` family through ``paddle_tpu/models/lfm2.py``."""
from __future__ import annotations

import re

# imported here, not inside build_train: a checkout whose program has
# no such family fails as this file is loaded, before any reference
# step is computed
from paddle_tpu.models.lfm2 import Lfm2MoeConfig, Lfm2MoeForCausalLM
from perf.reference import lfm2_moe as R

from . import common

_LEAVES = {
    "operator_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
    "conv.in_proj": "conv.in_proj.weight", "conv.taps": "conv.conv_weight",
    "conv.out_proj": "conv.out_proj.weight",
    "attn.q": "self_attn.q_proj.weight", "attn.k": "self_attn.k_proj.weight",
    "attn.v": "self_attn.v_proj.weight", "attn.o": "self_attn.out_proj.weight",
    "attn.q_norm": "self_attn.q_layernorm.weight",
    "attn.k_norm": "self_attn.k_layernorm.weight",
    "mlp.w1": "feed_forward.gate_proj.weight",
    "mlp.w3": "feed_forward.up_proj.weight",
    "mlp.w2": "feed_forward.down_proj.weight",
    "moe.router": "feed_forward.gate.weight",
    "moe.w1": "feed_forward.w1", "moe.w3": "feed_forward.w3",
    "moe.w2": "feed_forward.w2",
}


def program_name(leaf, layer):
    """A reference leaf's name among ``Lfm2MoeForCausalLM``'s
    parameters (the reference's leaves are per layer, so ``layer`` is
    always None)."""
    if leaf == "embed":
        return "lfm2.embed_tokens.weight"
    if leaf == "final_norm":
        return "lfm2.embedding_norm.weight"
    i, rest = re.match(r"layers\.(\d+)\.(.*)", leaf).groups()
    return f"lfm2.layer_{i}.{_LEAVES[rest]}"


def _model(cfg, **kw):
    if not cfg["tie_word_embeddings"]:
        raise ValueError("this adapter ties the head to the embedding")
    plan = R.plan(cfg)
    return Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=[op for op, _ in plan],
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_offset=cfg["expert_offset"],
        experts_held=cfg["num_experts"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        expert_bias=tuple(R.expert_bias(cfg)),
        conv_L_cache=cfg["conv_L_cache"], norm_eps=cfg["norm_eps"],
        rope_theta=cfg["rope_parameters"]["rope_theta"], **kw))


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
    return cfg["num_experts"] / cfg["published"]["num_experts"]


def expert_shape(cfg):
    """The held experts' products' shapes, for
    ``kernel_costs/expert_mlp``: the experts held here, the hidden
    width and an expert's width."""
    return dict(held=cfg["num_experts"], h=cfg["hidden_size"],
                i=cfg["moe_intermediate_size"])


def train_flops_per_token(cfg, batch):
    """6 x the parameters a token multiplies with + attention's scores
    and values: every parameter outside the experts once; of the held
    experts a token's ``num_experts_per_tok`` slots times the share of
    the router's slots that fall here (not all the experts held, let
    alone all the router's).  Norm weights and conv taps multiply
    elementwise and are left out; recomputed operations are not
    counted."""
    h = cfg["hidden_size"]
    expert = 3 * h * cfg["moe_intermediate_size"]
    n = cfg["vocab_size"] * h               # the tied head's product
    attn = 0
    for op, ffn in R.plan(cfg):
        if op == "conv":
            n += 4 * h * h
        else:
            d = h // cfg["num_attention_heads"]
            n += 2 * h * h + 2 * h * cfg["num_key_value_heads"] * d
            attn += 12 * h * batch["seq_len"]
        if ffn == "dense":
            n += 3 * h * cfg["intermediate_size"]
        else:
            n += h * cfg["published"]["num_experts"] \
                + cfg["num_experts_per_tok"] * routed_share(cfg) * expert
    return 6.0 * n + attn


def attention_shape(cfg, batch):
    """The flash-attention call's shapes in a training step: ``h`` is
    the query heads (the kernel's work follows them; each key/value
    head serves ``heads / kv_heads`` of them)."""
    return dict(b=batch["rows"], h=cfg["num_attention_heads"],
                sq=batch["seq_len"], sk=batch["seq_len"],
                d=cfg["hidden_size"] // cfg["num_attention_heads"],
                causal=True)


def expert_counters():
    """{layer: slots routed to each held expert, in order} and
    {layer: share of the router's slots routed here}, from the
    program's ``moe.*`` gauges; empty where it has none."""
    from paddle_tpu.observability import metrics
    moe = metrics.snapshot().get("moe", {})
    per_layer = {}
    for labels, value in moe.get("tokens_per_expert", {}).items():
        at = dict(kv.split("=") for kv in labels.split(","))
        per_layer.setdefault(at["layer"], {})[int(at["expert"])] = value
    tokens = {layer: [v for _, v in sorted(by.items())]
              for layer, by in per_layer.items()}
    shares = {labels.partition("=")[2]: value for labels, value
              in moe.get("routed_here_share", {}).items()}
    return tokens, shares


def expert_calls():
    """{layer: {call number, from 1: [slots routed to each held expert
    in that call, ..., slots the router filled]}} for the last calls
    the program kept, from ``moe.routed_by_call``; empty where the
    program keeps none."""
    from paddle_tpu.incubate.distributed.models import moe
    read = getattr(moe, "routed_by_call", None)
    return read() if read else {}
