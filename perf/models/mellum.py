"""The ``mellum`` family through ``paddle_tpu/models/mellum.py``."""
from __future__ import annotations

import re

# imported here, not inside build_train: a checkout whose program has
# no such family fails as this file is loaded, before any reference
# step is computed
from paddle_tpu.models.mellum import (OPERATOR, MellumConfig,
                                      MellumForCausalLM)
from perf import loader
from perf.reference import mellum as R

# the parameters' change is measured from the initial values as the
# program holds them (PERF.md section 6, PR 37), as Kimi-Linear's
from .kimi_linear import Program
# the sparse block's counters are the block's, whatever the family, and
# this configuration spells the held experts as LFM2's does
from .lfm2_moe import (expert_calls, expert_counters,  # noqa: F401
                       expert_shape)

_LEAVES = {
    "input_norm": "input_norm.weight", "ffn_norm": "ffn_norm.weight",
    **{f"attn.{x}": f"{{attention}}.{x}_proj.weight" for x in "qkvo"},
    "moe.router": "routed_experts.gate.weight",
    "moe.w1": "routed_experts.w1", "moe.w3": "routed_experts.w3",
    "moe.w2": "routed_experts.w2",
}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}


def _kind(i):
    """The published period from its start: every fourth layer full."""
    return "full_attention" if i % 4 == 3 else "sliding_attention"


def program_name(leaf, layer):
    """A reference leaf's name among ``MellumForCausalLM``'s parameters
    (the reference's leaves are per layer, so ``layer`` is always
    None).  A layer's attention lies under the attribute its type gives
    it, and the layers kept follow the published period from its start
    (``_model`` holds a configuration to that)."""
    if leaf in _TOP:
        return _TOP[leaf]
    i, rest = re.match(r"layers\.(\d+)\.(.*)", leaf).groups()
    return f"model.layer_{i}." + _LEAVES[rest].format(
        attention=OPERATOR[_kind(int(i))])


def _model(cfg, **kw):
    if cfg["tie_word_embeddings"] or cfg["attention_bias"] \
            or not cfg["norm_topk_prob"] or not cfg["use_sliding_window"]:
        raise ValueError("this adapter builds an untied head, no biases, "
                         "renormalised top-k weights and window layers")
    kinds = R.plan(cfg)
    if kinds != [_kind(i) for i in range(len(kinds))]:
        raise ValueError(f"the layers kept do not follow the published "
                         f"period from its start: {kinds}")
    return MellumForCausalLM(MellumConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(kinds),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_parameters=cfg["rope_parameters"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        expert_offset=cfg["expert_offset"],
        experts_held=cfg["num_experts"],
        train_router=cfg["train_router"],
        expert_slots_at_a_time=cfg["expert_slots_at_a_time"],
        norm_eps=cfg["rms_norm_eps"], **kw))


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


def attention_shape(cfg, batch):
    """A FULL layer's flash-attention call in a training step, for
    ``kernel_costs/flash_attention`` (the window layers' kernels carry
    other names and ``window_shape`` is theirs): ``h`` is the query
    heads, which the kernel's work follows."""
    return dict(b=batch["rows"], h=cfg["num_attention_heads"],
                sq=batch["seq_len"], sk=batch["seq_len"],
                d=cfg["head_dim"], causal=True)


def window_shape(cfg, batch):
    """A WINDOW layer's call, for ``kernel_costs/window_attention``."""
    return dict(b=batch["rows"], h=cfg["num_attention_heads"],
                kv=cfg["num_key_value_heads"], s=batch["seq_len"],
                d=cfg["head_dim"], window=cfg["sliding_window"])


def train_flops_per_token(cfg, batch):
    """6 x the parameters a token multiplies with + attention's scores
    and values: the four attention projections, the router and the head
    once (the embedding is a lookup; norm weights multiply elementwise
    and are left out); of the held experts a token's
    ``num_experts_per_tok`` slots times the share of the router's slots
    that fall here.  Attention's own products, forward and twice that
    backward, over the score pairs a layer NEEDS: half the square in a
    full layer, the band in a window layer
    (``kernel_costs/window_attention.pairs``), so that a window layer
    counts a quarter of a full one at 8,192 positions and 1,024 keys.
    Where ``train_router`` is false the router's product runs forward
    only (a third of the 6).  Recomputed operations are not counted."""
    h, heads, d = (cfg["hidden_size"], cfg["num_attention_heads"],
                   cfg["head_dim"])
    s = batch["seq_len"]
    band = loader.module("kernel_costs", "window_attention").pairs(
        s, cfg["sliding_window"])
    expert = 3 * h * cfg["moe_intermediate_size"]
    layer = (2 * h * heads * d + 2 * h * cfg["num_key_value_heads"] * d
             + h * cfg["published"]["num_experts"]
             * (1 if cfg["train_router"] else 1 / 3)
             + cfg["num_experts_per_tok"] * routed_share(cfg) * expert)
    n = cfg["vocab_size"] * h               # the head's product
    attn = 0.0
    for kind in R.plan(cfg):
        n += layer
        pairs = band if kind == "sliding_attention" else s * s / 2
        # two products of 2 d multiply-adds' flops a pair and head
        # forward, four backward
        attn += 6 * 2 * heads * d * pairs / s
    return 6.0 * n + attn
