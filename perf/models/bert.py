"""The ``bert`` family through ``paddle_tpu/models/bert.py``."""
from __future__ import annotations

from . import common

_TOP = {"word": "bert.embeddings.word.weight",
        "position": "bert.embeddings.position.weight",
        "token_type": "bert.embeddings.token_type.weight",
        "emb_ln.weight": "bert.embeddings.ln.weight",
        "emb_ln.bias": "bert.embeddings.ln.bias",
        "pooler.weight": "bert.pooler.weight",
        "pooler.bias": "bert.pooler.bias"}


def program_name(leaf, layer):
    """A reference leaf's name among ``BertForPretraining``'s
    parameters."""
    if layer is not None:
        return f"bert.layer_{layer}.{leaf[len('blocks.'):]}"
    return _TOP.get(leaf, leaf)


def build_train(cfg, batch):
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    prec = cfg["precision"]["train"]
    model = BertForPretraining(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        intermediate_size=cfg["intermediate_size"], dropout=0.0,
        layer_norm_eps=cfg["layer_norm_eps"],
        use_flash_attention=prec["flash_attention"], recompute=True,
        recompute_policy=prec["recompute_policy"],
        max_predictions=batch["masked_per_row"]))
    return common.TrainProgram(
        model, prec,
        lambda m, ids, seg, mlm, nsp: m(ids, seg, mlm, nsp))


def train_flops_per_token(cfg, batch):
    """6 N + 12 L H S, with the tied vocabulary head counted only on
    the share of positions that are projected (the masked ones)."""
    head = cfg["vocab_size"] * cfg["hidden_size"]
    share = batch["masked_per_row"] / batch["seq_len"]
    return 6.0 * (cfg["parameters"] - head) + 6.0 * head * share \
        + 12.0 * cfg["num_hidden_layers"] * cfg["hidden_size"] \
        * batch["seq_len"]


def attention_shape(cfg, batch):
    """The flash-attention call's shapes in a training step."""
    return dict(b=batch["rows"], h=cfg["num_attention_heads"],
                sq=batch["seq_len"], sk=batch["seq_len"],
                d=cfg["hidden_size"] // cfg["num_attention_heads"],
                causal=False)
