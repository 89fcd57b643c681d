"""The ``gpt2`` family through ``paddle_tpu/models/gpt.py``."""
from __future__ import annotations

from . import common


def program_name(leaf, layer):
    """A reference leaf's name among ``GPTForCausalLM``'s parameters."""
    if layer is not None:
        return f"gpt.block_{layer}.{leaf[len('blocks.'):]}"
    return {"wte": "gpt.wte.weight", "wpe": "gpt.wpe.weight"}.get(
        leaf, f"gpt.{leaf}")


def _model(cfg, **kw):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the gpt2 family ties its head to wte")
    return GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_seq_len=cfg["n_positions"], intermediate_size=cfg["n_inner"],
        dropout=0.0, layer_norm_eps=cfg["layer_norm_epsilon"],
        tie_embeddings=True, **kw))


def build_train(cfg, batch):
    prec = cfg["precision"]["train"]
    model = _model(cfg, use_flash_attention=prec["flash_attention"],
                   recompute=True,
                   recompute_policy=prec["recompute_policy"])
    return common.TrainProgram(
        model, prec, lambda m, ids, labels: m(ids, labels))


def build_serve(cfg):
    model = _model(cfg)
    model.eval()
    return model


def train_flops_per_token(cfg, batch):
    """6 N + 12 L H S: forward and backward of every parameter's
    multiply-add, plus attention's scores and values; recomputed
    operations are not counted."""
    return 6.0 * cfg["parameters"] + 12.0 * cfg["n_layer"] \
        * cfg["n_embd"] * batch["seq_len"]


def attention_shape(cfg, batch):
    """The flash-attention call's shapes in a training step."""
    return dict(b=batch["rows"], h=cfg["n_head"], sq=batch["seq_len"],
                sk=batch["seq_len"], d=cfg["n_embd"] // cfg["n_head"],
                causal=True)


def kv_shape(cfg):
    """The paged KV cache's shape per token, as the engine holds it."""
    return dict(layers=cfg["n_layer"], kv_heads=cfg["n_head"],
                q_heads=cfg["n_head"],
                head_dim=cfg["n_embd"] // cfg["n_head"])
