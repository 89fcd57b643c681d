"""GPT-family decoder-only language model — the flagship trainable.

Capability analog of the GPT/LLaMA configs the reference trains through
fleet hybrid parallelism (SURVEY §6 configs 4-5; the reference keeps model
defs downstream in PaddleNLP, e.g. its ``GPTForPretraining``, but the
training mechanics — VocabParallelEmbedding / Column-RowParallelLinear
sharding, flash attention, recompute — are reference in-tree features:
``python/paddle/distributed/fleet/layers/mpu/mp_layers.py:47,333,540``,
``python/paddle/nn/functional/flash_attention.py:147``,
``python/paddle/distributed/fleet/recompute/recompute.py:404``).

TPU-native: one model class, parallelism applied *afterwards* as GSPMD
sharding (``shard_gpt``) instead of swapping layer classes — the mesh axes
decide dp/tp/sp; XLA's partitioner emits the Megatron collectives.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from ..core import scope as _scope
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layers import Dropout, Embedding, LayerNorm, Linear


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    intermediate_size: int = 0  # 0 -> 4*hidden
    dropout: float = 0.0
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    use_flash_attention: bool = True
    recompute: bool = False  # activation recompute per block (jax.checkpoint)
    recompute_policy: str = "full"  # or "dots_saveable" (keep matmul outs)
    # MoE (0 = dense FFN). Experts shard over the ep axis via shard_gpt.
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def _init_normal(std):
    return I.Normal(mean=0.0, std=std)


def _glue_fusion() -> bool:
    """train_glue_fusion flag (ISSUE 19): fused residual+norm glue
    kernels in the TRAINING forward. Read per forward — one dict
    lookup; eval/serving paths never consult it (callers also gate on
    ``self.training``)."""
    from ..core import state
    return bool(state.get_flag("train_glue_fusion"))


class GPTAttention(Layer):
    """Causal self-attention with a fused qkv projection (the shape the
    reference fuses in ``fused_attention``-family kernels, SURVEY C12)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.qkv = Linear(h, 3 * h, weight_attr=_init_normal(0.02))
        self.proj = Linear(
            h, h, weight_attr=_init_normal(0.02 / math.sqrt(2 * cfg.num_layers)))
        self.dropout = cfg.dropout
        self.use_flash = cfg.use_flash_attention
        # context parallelism (ring attention over an sp mesh axis) —
        # wired by shard_gpt(..., context_parallel=True)
        self._cp_mesh = None
        self._cp_axes = (None, None, None)  # (sp, dp, mp)

    def forward(self, x):
        from .. import ops
        b, s, h = x.shape
        qkv = self.qkv(x)
        qkv = ops.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        q, k, v = ops.unbind(qkv, axis=2)  # each [b, s, heads, head_dim]
        if self._cp_mesh is not None:
            sp, dp, mp = self._cp_axes
            out = F.ring_flash_attention(
                q, k, v, mesh=self._cp_mesh, sp_axis=sp, batch_axes=dp,
                head_axis=mp, is_causal=True)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.dropout if self.training else 0.0,
                backend=None if self.use_flash else "xla")
        out = ops.reshape(out, [b, s, h])
        return self.proj(out)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc1 = Linear(cfg.hidden_size, cfg.intermediate_size,
                          weight_attr=_init_normal(0.02))
        self.fc2 = Linear(
            cfg.intermediate_size, cfg.hidden_size,
            weight_attr=_init_normal(0.02 / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        return self.fc2(F.gelu(self.fc1(x), approximate=True))


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        if cfg.num_experts > 0:
            from ..incubate.distributed.models.moe import MoEMLP
            self.mlp = MoEMLP(cfg.hidden_size, cfg.intermediate_size,
                              cfg.num_experts, top_k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor)
        else:
            self.mlp = GPTMLP(cfg)
        self.drop = Dropout(cfg.dropout)
        self._recompute = cfg.recompute
        self._recompute_policy = (cfg.recompute_policy
                                  if cfg.recompute_policy != "full"
                                  else None)

    def _inner(self, x):
        x = x + self.drop(self.attn(self.ln1(x)))
        x = x + self.drop(self.mlp(self.ln2(x)))
        return x

    def forward(self, x):
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            return recompute(self._inner, x, policy=self._recompute_policy)
        return self._inner(x)

    def _inner_fused(self, x, pending=None):
        """Glue-fused twin of ``_inner`` (train_glue_fusion, ISSUE 19).
        Pre-norm blocks can't fuse their OWN ln1 with a residual add —
        the add that feeds ln1 belongs to the previous block — so the
        model loop threads the previous block's un-added MLP branch in
        as ``pending``: (x+pending -> ln1) and (x+attn -> ln2) each run
        as ONE fused dispatch, and the block returns its own MLP branch
        un-added for the next block (the final add fuses with ln_f).
        Four glue dispatches per layer (add, ln1, add, ln2) become
        two."""
        if pending is None:
            h1 = self.ln1(x)
        else:
            x, h1 = F.fused_residual_norm(
                x, pending, self.ln1.weight, self.ln1.bias,
                epsilon=self.ln1._epsilon)
        a = self.drop(self.attn(h1))
        x, h2 = F.fused_residual_norm(
            x, a, self.ln2.weight, self.ln2.bias,
            epsilon=self.ln2._epsilon)
        return x, self.drop(self.mlp(h2))

    def forward_fused(self, x, pending=None):
        """(x, pending) -> (x, pending') for the glue-fused train loop;
        composes with block recompute (the pending branch rides as an
        extra checkpointed tensor arg)."""
        if self._recompute and self.training:
            from ..distributed.fleet.recompute import recompute
            if pending is None:
                return recompute(self._inner_fused, x,
                                 policy=self._recompute_policy)
            return recompute(self._inner_fused, x, pending,
                             policy=self._recompute_policy)
        return self._inner_fused(x, pending)


class GPTModel(Layer):
    """Embeddings + transformer stack + final norm -> hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                             weight_attr=_init_normal(0.02))
        self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size,
                             weight_attr=_init_normal(0.02))
        self.drop = Dropout(cfg.dropout)
        self.blocks = [GPTBlock(cfg) for _ in range(cfg.num_layers)]
        for i, blk in enumerate(self.blocks):
            self.add_sublayer(f"block_{i}", blk)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids):
        from .. import ops
        s = input_ids.shape[1]
        pos = ops.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if self.training and self.blocks and _glue_fusion():
            pending = None
            for blk in self.blocks:
                x, pending = blk.forward_fused(x, pending)
            # the last block's MLP branch fuses into the final norm
            _, h = F.fused_residual_norm(
                x, pending, self.ln_f.weight, self.ln_f.bias,
                epsilon=self.ln_f._epsilon)
            return h
        for blk in self.blocks:
            x = blk(x)
        return self.ln_f(x)


class GPTForCausalLM(Layer):
    """LM head on top; ``forward(ids, labels)`` returns mean next-token
    cross-entropy (labels already shifted by the data pipeline, as in the
    reference pretrain loaders)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if cfg.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                                  weight_attr=_init_normal(0.02),
                                  bias_attr=False)

    def logits(self, input_ids) -> Tensor:
        from .. import ops
        h = self.gpt(input_ids)
        if self.lm_head is not None:
            return self.lm_head(h)
        with _scope.phase("lm_head"):
            return ops.matmul(h, self.gpt.wte.weight, transpose_y=True)

    def forward(self, input_ids, labels=None):
        logits = self.logits(input_ids)
        if labels is None:
            return logits
        loss = F.cross_entropy(
            ops_reshape(logits, [-1, self.cfg.vocab_size]),
            ops_reshape(labels, [-1]))
        if self.cfg.num_experts > 0 and self.cfg.moe_aux_weight:
            from .. import ops
            for blk in self.gpt.blocks:
                aux = getattr(blk.mlp, "aux_loss", None)
                if aux is not None:
                    loss = loss + self.cfg.moe_aux_weight * aux
        return loss

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    def flops_per_token(self, seq_len: int) -> float:
        """Standard 6N + attention estimate (per trained token)."""
        n = self.num_params()
        c = self.cfg
        attn = 12 * c.num_layers * c.hidden_size * seq_len
        return 6.0 * n + attn


def ops_reshape(x, shape):
    from .. import ops
    return ops.reshape(x, shape)


class GPTForCausalLMPipe(Layer):
    """Pipeline-parallel GPT (analog of the reference trainers'
    ``GPTForCausalLMPipe`` built on ``PipelineLayer``, and of SURVEY
    D15-D17). The transformer stack runs as an SPMD GPipe over the
    ``pp_axis`` (see ``fleet/pipeline.py``); embeddings, final norm and
    the tied LM head stay outside the pipelined region on their own
    shardings (dp over batch)."""

    # canonical Megatron TP split of a STACKED [L, ...] GPT block
    # (column-parallel qkv/fc1, row-parallel proj/fc2) — the tp_rules
    # PipelinedBlocks.shard consumes for the pp x mp hybrid
    TP_RULES = {
        "attn.qkv.weight": 2, "attn.qkv.bias": 1,
        "mlp.fc1.weight": 2, "mlp.fc1.bias": 1,
        "attn.proj.weight": 1, "mlp.fc2.weight": 1,
    }

    def __init__(self, cfg: GPTConfig, mesh, pp_axis: str = "pp",
                 dp_axis=None, num_microbatches: int = 1, interleave=1,
                 tp_axis=None, tp_rules=None):
        super().__init__()
        if cfg.dropout:
            raise NotImplementedError(
                "pipelined GPT requires dropout=0 (single-program "
                "pipelining threads parameters, not RNG state)")
        from dataclasses import replace

        from ..distributed.fleet.pipeline import PipelinedBlocks

        self.cfg = cfg
        self.dp_axis = dp_axis
        blk_cfg = replace(cfg, recompute=False)  # pipeline owns remat
        self.wte = Embedding(cfg.vocab_size, cfg.hidden_size,
                             weight_attr=_init_normal(0.02))
        self.wpe = Embedding(cfg.max_seq_len, cfg.hidden_size,
                             weight_attr=_init_normal(0.02))
        self.blocks = PipelinedBlocks(lambda: GPTBlock(blk_cfg),
                                      cfg.num_layers, mesh=mesh,
                                      pp_axis=pp_axis,
                                      num_microbatches=num_microbatches,
                                      interleave=interleave)
        if tp_axis is not None:
            # Megatron TP inside the pipeline (pp x mp hybrid): re-shard
            # the stacked leaves with the tensor-split placements; the
            # pipeline's shard_map leaves tp_axis to GSPMD
            self.blocks.shard(mesh, pp_axis, tp_axis=tp_axis,
                              tp_rules=tp_rules or self.TP_RULES)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def logits(self, input_ids) -> Tensor:
        from .. import ops
        s = input_ids.shape[1]
        pos = ops.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.blocks(x, batch_axes=self.dp_axis)
        h = self.ln_f(x)
        return ops.matmul(h, self.wte.weight, transpose_y=True)

    def forward(self, input_ids, labels=None):
        logits = self.logits(input_ids)
        if labels is None:
            return logits
        return F.cross_entropy(
            ops_reshape(logits, [-1, self.cfg.vocab_size]),
            ops_reshape(labels, [-1]))

    def train_batch(self, input_ids, labels):
        """Fused 1F1B step (reference ``pipeline_parallel.py:663``):
        the epilogue (final norm + tied LM head + CE) runs INSIDE the
        schedule on the last stage via ``post_params``, so ln_f and the
        tied embedding get their head-path grads; the embedding path's
        grads arrive through ``x``'s cotangent. ``loss.backward()``
        then ``optimizer.step()`` as usual."""
        import jax
        import jax.numpy as jnp

        from .. import ops
        from ..distributed.fleet.pipeline import functional_call

        def loss_fn(y, tgt, post_vals):
            w_ln, b_ln, wte = post_vals
            # run the real ln_f purely on the traced values (no drift
            # from a hand-rolled copy of LayerNorm's math)
            h = functional_call(self.ln_f,
                                {"weight": w_ln, "bias": b_ln}, y)
            logits = jnp.einsum("bsh,vh->bsv", h, wte)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            ll = jnp.take_along_axis(logp, tgt[..., None].astype(
                jnp.int32), axis=-1)
            return -jnp.mean(ll)

        s = input_ids.shape[1]
        pos = ops.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        return self.blocks.train_batch(
            x, labels, loss_fn, batch_axes=self.dp_axis,
            post_params=[self.ln_f.weight, self.ln_f.bias,
                         self.wte.weight])

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())


# --- GSPMD sharding recipe (the fleet-TP analog for this model) ------------

def shard_gpt(model: GPTForCausalLM, mesh, dp_axis="dp", mp_axis="mp",
              sp_axis=None, context_parallel=False, ep_axis=None):
    """Pin Megatron-style shardings over ``mesh`` (a ProcessMesh).

    Column-parallel: qkv / fc1 weights shard output dim over mp.
    Row-parallel: proj / fc2 weights shard input dim over mp.
    Vocab-parallel: wte shards vocab dim over mp.
    XLA's SPMD partitioner then inserts the identity/allreduce pairs the
    reference hand-codes in ``mp_ops.py`` (SURVEY D14). dp/sp axes shard the
    *data* (batch/sequence), applied by the caller on inputs; parameters
    stay replicated over dp/sp (pure DP; use fleet sharding stages for ZeRO).

    ``context_parallel=True`` (requires ``sp_axis``) switches every attention
    layer to ring attention over the sp axis — K/V blocks rotate on ICI and
    the [S, S] score matrix never materializes, the long-context mode (the
    reference's sep/segment-parallel axis, ``fleet/base/topology.py:65``).
    """
    from ..distributed.auto_parallel.api import (Replicate, Shard,
                                                 shard_parameter)

    names = mesh.dim_names
    if ep_axis is not None and ep_axis in names:
        from ..incubate.distributed.models.moe import MoEMLP
        for blk in model.gpt.blocks:
            if isinstance(blk.mlp, MoEMLP):
                blk.mlp.shard(mesh, ep_axis)
    if context_parallel:
        if sp_axis not in names:
            raise ValueError("context_parallel requires sp_axis in the mesh")
        for blk in model.gpt.blocks:
            blk.attn._cp_mesh = mesh
            blk.attn._cp_axes = (
                sp_axis,
                dp_axis if dp_axis in names else None,
                mp_axis if mp_axis in names else None)
    if mp_axis not in names:
        return model
    mp_dim = names.index(mp_axis)

    def pl(tensor_dim):
        p = [Replicate()] * mesh.ndim
        p[mp_dim] = Shard(tensor_dim)
        return p

    rep = [Replicate()] * mesh.ndim
    shard_parameter(model.gpt.wte.weight, mesh, pl(0))
    shard_parameter(model.gpt.wpe.weight, mesh, rep)
    for blk in model.gpt.blocks:
        shard_parameter(blk.attn.qkv.weight, mesh, pl(1))
        shard_parameter(blk.attn.qkv.bias, mesh, pl(0))
        shard_parameter(blk.attn.proj.weight, mesh, pl(0))
        shard_parameter(blk.attn.proj.bias, mesh, rep)
        if hasattr(blk.mlp, "fc1"):  # dense FFN (MoE shards over ep above)
            shard_parameter(blk.mlp.fc1.weight, mesh, pl(1))
            shard_parameter(blk.mlp.fc1.bias, mesh, pl(0))
            shard_parameter(blk.mlp.fc2.weight, mesh, pl(0))
            shard_parameter(blk.mlp.fc2.bias, mesh, rep)
    if model.lm_head is not None:
        shard_parameter(model.lm_head.weight, mesh, pl(1))
    return model
