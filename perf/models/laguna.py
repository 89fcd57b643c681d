"""The ``laguna`` family through ``paddle_tpu/models/laguna.py``."""
from __future__ import annotations

import re

# imported here, not inside build_train: a checkout whose program has
# no such family fails as this file is loaded, before any reference
# step is computed
from paddle_tpu.models.laguna import LagunaConfig, LagunaForCausalLM
from paddle_tpu.models.mellum import OPERATOR
from perf import loader
from perf.reference import laguna as R

# the parameters' change is measured from the initial values as the
# program holds them (PERF.md section 6, PR 37), as Kimi-Linear's
from .kimi_linear import Program
# the sparse block's counters are the block's, whatever the family, and
# this configuration spells the held experts as LFM2's does
from .lfm2_moe import (expert_calls, expert_counters,  # noqa: F401
                       expert_shape, routed_share)

_MLP = {"w1": "gate_proj.weight", "w3": "up_proj.weight",
        "w2": "down_proj.weight"}
_LEAVES = {
    "input_norm": "input_norm.weight", "ffn_norm": "ffn_norm.weight",
    **{f"attn.{x}": f"{{attention}}.{x}_proj.weight" for x in "qkvgo"},
    "moe.router": "routed_experts.gate.weight",
    "moe.w1": "routed_experts.w1", "moe.w3": "routed_experts.w3",
    "moe.w2": "routed_experts.w2",
    **{f"mlp.{k}": f"mlp.{v}" for k, v in _MLP.items()},
    **{f"shared.{k}": f"shared_expert.{v}" for k, v in _MLP.items()},
}
_TOP = {"embed": "model.embed_tokens.weight", "head": "lm_head.weight",
        "final_norm": "model.norm.weight"}


def _kind(i):
    """The published period from its start: every fourth layer full,
    layer 0 the first of them."""
    return "full_attention" if i % 4 == 0 else "sliding_attention"


def program_name(leaf, layer):
    """A reference leaf's name among ``LagunaForCausalLM``'s parameters
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
            or not cfg["gating"] or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("this adapter builds an untied head, no biases, "
                         "gated attention and weights on the experts' "
                         "output")
    if cfg["shared_expert_intermediate_size"] % cfg["moe_intermediate_size"]:
        raise ValueError("the shared expert is a whole number of routed "
                         "experts' widths")
    plan = R.plan(cfg)
    kinds = [kind for kind, _, _ in plan]
    ffns = [ffn for _, ffn, _ in plan]
    if kinds != [_kind(i) for i in range(len(plan))]:
        raise ValueError(f"the layers kept do not follow the published "
                         f"period from its start: {kinds}")
    if ffns != sorted(ffns):
        raise ValueError(f"dense layers lead: {ffns}")
    return LagunaForCausalLM(LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(kinds),
        num_heads_per_layer=tuple(heads for _, _, heads in plan),
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_parameters={k: cfg["rope_parameters"][k] for k in OPERATOR},
        first_k_dense_replace=ffns.count("dense"),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["shared_expert_intermediate_size"]
        // cfg["moe_intermediate_size"],
        n_routed_experts=cfg["published"]["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["moe_routed_scaling_factor"],
        router_norm_eps=R.ROUTER_NORM_EPS,
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


def _heads(cfg, kind):
    """The query heads of the kept layers of one type (one count a
    type, or the kernels' shapes below are not one call's)."""
    heads = {n for k, _, n in R.plan(cfg) if k == kind}
    if len(heads) != 1:
        raise ValueError(f"{kind} layers with {sorted(heads)} heads")
    return heads.pop()


def attention_shape(cfg, batch):
    """A FULL layer's flash-attention call in a training step, for
    ``kernel_costs/flash_attention`` (the window layers' kernels carry
    other names and ``window_shape`` is theirs): ``h`` is the full
    layers' query heads, which the kernel's work follows."""
    return dict(b=batch["rows"], h=_heads(cfg, "full_attention"),
                sq=batch["seq_len"], sk=batch["seq_len"],
                d=cfg["head_dim"], causal=True)


def window_shape(cfg, batch):
    """A WINDOW layer's call, for ``kernel_costs/window_attention``."""
    return dict(b=batch["rows"], h=_heads(cfg, "sliding_attention"),
                kv=cfg["num_key_value_heads"], s=batch["seq_len"],
                d=cfg["head_dim"], window=cfg["sliding_window"])


def train_flops_per_token(cfg, batch):
    """6 x the parameters a token multiplies with + attention's scores
    and values.  Per layer, by ITS head count: the q and o projections,
    k and v, and the gate's projection (hidden x heads; the gate's
    elementwise product, as norm weights, is left out); the dense MLP
    in a ``dense`` layer; in a ``sparse`` one the router, the shared
    expert, and of the held experts a token's ``num_experts_per_tok``
    slots times the share of the router's slots that fall here.  The
    head once (the embedding is a lookup).  Attention's own products,
    forward and twice that backward, over the score pairs a layer
    NEEDS: half the square in a full layer, the band in a window layer
    (``kernel_costs/window_attention.pairs``).  Where ``train_router``
    is false the router's product runs forward only (a third of the
    6).  Recomputed operations are not counted."""
    h, d, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    s = batch["seq_len"]
    band = loader.module("kernel_costs", "window_attention").pairs(
        s, cfg["sliding_window"])
    sparse = (h * cfg["published"]["num_experts"]
              * (1 if cfg["train_router"] else 1 / 3)
              + 3 * h * cfg["shared_expert_intermediate_size"]
              + cfg["num_experts_per_tok"] * routed_share(cfg)
              * 3 * h * cfg["moe_intermediate_size"])
    n = cfg["vocab_size"] * h               # the head's product
    attn = 0.0
    for kind, ffn, heads in R.plan(cfg):
        n += 2 * h * heads * d + 2 * h * kv * d + h * heads
        n += 3 * h * cfg["intermediate_size"] if ffn == "dense" else sparse
        pairs = band if kind == "sliding_attention" else s * s / 2
        # two products of 2 d multiply-adds' flops a pair and head
        # forward, four backward
        attn += 6 * 2 * heads * d * pairs / s
    return 6.0 * n + attn
