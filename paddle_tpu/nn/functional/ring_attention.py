"""Ring attention — context parallelism for long sequences.

Capability analog of the reference's segment-parallel (sep) long-context
path (SURVEY §5 long-context row; reference hybrid topology's sep axis,
``python/paddle/distributed/fleet/base/topology.py:65`` ["data", "pipe",
"sharding", "sep", "model"], and the RingFlashAttention used by its
downstream trainers). TPU-native mechanism: one ``jax.shard_map`` over the
sequence-parallel mesh axis; K/V blocks rotate around the ring via
``lax.ppermute`` (ICI neighbor exchange) while each device holds its Q
block and maintains flash-style online-softmax accumulators. The whole
ring is a ``lax.scan``, so XLA overlaps the permute of step j+1 with the
matmul of step j, and JAX autodiff transposes the ring for the backward
pass (reverse-direction permutes) — no hand-written backward kernel.

Memory: with ``jax.checkpoint`` on the scan body (default), residuals per
step are O(block) and the [S, S] score matrix never materializes — the
context-parallel analog of flash attention's tiling.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ...core.dispatch import apply


from ...core.meshutil import pvary as _pvary_impl


def _pvary(xs, axes):
    return _pvary_impl(xs, axes)


def _ring_attention_local(q, k, v, axis, causal, scale, remat=True,
                          mesh_axes=()):
    """Runs INSIDE shard_map: q/k/v are the local blocks [B, S_loc, H, D]
    (kv heads may be fewer — GQA repeats them)."""
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    s_loc = q.shape[1]
    hq, hk = q.shape[2], k.shape[2]
    if hk != hq:
        k = jnp.repeat(k, hq // hk, axis=2)
        v = jnp.repeat(v, hq // hk, axis=2)
    sc = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    qf = jnp.swapaxes(q, 1, 2).astype(jnp.float32)  # [B, H, Sq, D]
    perm = [(r, (r + 1) % n) for r in range(n)]

    b, h = qf.shape[0], qf.shape[1]
    o0 = jnp.zeros((b, h, s_loc, q.shape[-1]), jnp.float32)
    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # constants enter the scan carry as device-invariant; steps make them
    # varying (axis_index masks) — mark them varying up front for shard_map's
    # manual-axes type system
    o0, m0, l0 = _pvary((o0, m0, l0), tuple(mesh_axes))
    pos_q = i * s_loc + jnp.arange(s_loc)  # global positions (contiguous
    # Shard(1) layout; causal load is imbalanced across ranks — the
    # balanced zigzag layout is a possible refinement)

    def body(carry, j):
        o, m, l, kb, vb = carry
        src = (i - j) % n
        kf = jnp.swapaxes(kb, 1, 2).astype(jnp.float32)
        vf = jnp.swapaxes(vb, 1, 2).astype(jnp.float32)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                            preferred_element_type=jnp.float32) * sc
        if causal:
            pos_k = src * s_loc + jnp.arange(kb.shape[1])
            mask = pos_q[:, None] >= pos_k[None, :]
            logits = jnp.where(mask, logits, -jnp.inf)
        blk_max = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_max)
        # fully-masked blocks keep new_m = -inf: guard exp(-inf - -inf)
        safe_m = jnp.where(jnp.isneginf(new_m), 0.0, new_m)
        p = jnp.exp(logits - safe_m[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l = l * alpha + jnp.sum(p, axis=-1)
        o = o * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vf)
        kb, vb = lax.ppermute((kb, vb), axis, perm)
        return (o, jnp.maximum(m, blk_max), l, kb, vb), None

    if remat:
        body = jax.checkpoint(body)
    (o, m, l, _, _), _ = lax.scan(body, (o0, m0, l0, k, v), jnp.arange(n))
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_flash_attention(query, key, value, mesh=None, sp_axis="sp",
                         batch_axes=None, head_axis=None, is_causal=False,
                         scale=None, remat=True, name=None):
    """Context-parallel attention over a mesh ring.

    Args mirror ``scaled_dot_product_attention`` (paddle layout
    [batch, seq, num_heads, head_dim]) plus the mesh wiring:

    - ``mesh``: a ``ProcessMesh`` (or ``jax.sharding.Mesh``) containing
      ``sp_axis``.
    - ``sp_axis``: mesh axis the sequence dim is sharded over (the ring).
    - ``batch_axes``: optional mesh axis (or tuple) the batch dim is
      sharded over (dp), so the shard_map composes with data parallelism.
    - ``head_axis``: optional mesh axis the head dim is sharded over (mp),
      composing with tensor parallelism.

    Each device computes its Q block against every K/V block as the ring
    rotates; online softmax keeps the result exact (not approximate).
    """
    jmesh = getattr(mesh, "jmesh", mesh)
    if jmesh is None:
        raise ValueError("ring_flash_attention requires a mesh")
    if sp_axis not in jmesh.axis_names:
        raise ValueError(f"mesh has no axis {sp_axis!r}")

    bspec = batch_axes
    spec = P(bspec, sp_axis, head_axis, None)

    def impl(q, k, v):
        fn = partial(_ring_attention_local, axis=sp_axis, causal=is_causal,
                     scale=scale, remat=remat,
                     mesh_axes=tuple(jmesh.axis_names))
        from ...core.meshutil import shard_map as _shard_map
        sm = _shard_map(fn, mesh=jmesh, in_specs=(spec, spec, spec),
                        out_specs=spec)
        return sm(q, k, v)

    return apply("ring_flash_attention", impl, query, key, value)
