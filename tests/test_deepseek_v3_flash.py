"""The flash kernel pair at unequal key / value widths (PR 35), as the
deepseek_v3 family's latent attention needs them (keys 24 = 16 + 8,
values 16), against plain attention in float32 on the CPU:
tests/test_deepseek_v3.py has the family itself; the halves share no
model, and together they were over 200 s of tier-1.

Tolerances.  2e-5 relative to the largest entry holds outputs and
gradients, in different orders of summation; the kernel against its
unjitted twin is bitwise.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as FA

TOL = 2e-5


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    gap = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert gap <= tol, gap


def _plain_attention(q, k, v, seg=None):
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision="highest") / math.sqrt(q.shape[-1])
    n = q.shape[1]
    keep = jnp.tril(jnp.ones((n, n), bool))[None, None]
    if seg is not None:
        keep = keep & (seg[:, None, :, None] == seg[:, None, None, :])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")


def _qkv(kv_heads, d=24, dv=16, s=40, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal(shape), jnp.float32)
                 for shape in ((2, s, 4, d), (2, s, kv_heads, d),
                               (2, s, kv_heads, dv), (2, s, 4, dv)))


@pytest.mark.parametrize("kv_heads", [4, 2])
@pytest.mark.parametrize("segments", [False, True])
def test_flash_attention_at_unequal_widths(kv_heads, segments):
    """Forward and all three gradients against plain attention with
    24-wide keys and 16-wide values (tiles of 16: a diagonal, a padded
    tail), with and without GQA and segment ids; and bitwise against
    the unjitted twin of the fused backward."""
    q, k, v, w = _qkv(kv_heads)
    seg = jnp.asarray(np.repeat([[0] * 15 + [1] * 25], 2, 0)) \
        if segments else None

    def flash(q, k, v):
        return FA.flash_attention(q, k, v, causal=True, interpret=True,
                                  segment_ids=seg, blocks=(16, 16))

    out = flash(q, k, v)
    assert out.shape == (2, 40, 4, 16)
    close(out, _plain_attention(q, k, v, seg))
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_plain_attention(*a, seg) * w).sum(),
                    (0, 1, 2))(q, k, v)
    for g, r, like in zip(got, want, (q, k, v)):
        assert g.shape == like.shape        # dq, dk as q; dv as v
        close(g, r)
    _, lse = FA._fwd(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), seg, seg,
                     1 / math.sqrt(24), True, True, (16, 16))
    twin = FA.flash_attention_bwd_jnp(q, k, v, w, out, lse, causal=True,
                                      segment_ids=seg, blocks=(16, 16))
    for g, t in zip(got, twin):
        assert np.array_equal(np.asarray(g), np.asarray(t))


def test_flash_attention_at_equal_widths_is_what_it_was():
    """Values as wide as the keys take the same walk as before the
    second width: the kernel is bitwise its twin, whose tile arithmetic
    at equal widths is unchanged, the autotune key and the
    ``flash.tiles`` label name one width, and the VMEM the backward asks
    for is what it asked for."""
    q, k, _, _ = _qkv(4, d=16, dv=16)
    v, w = k + 1.0, q - 1.0

    def flash(q, k, v):
        return FA.flash_attention(q, k, v, causal=True, interpret=True,
                                  blocks=(16, 16))

    out = flash(q, k, v)
    got = jax.grad(lambda *a: (flash(*a) * w).sum(), (0, 1, 2))(q, k, v)
    _, lse = FA._fwd(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)), None, None,
                     0.25, True, True, (16, 16))
    twin = FA.flash_attention_bwd_jnp(q, k, v, w, out, lse, causal=True,
                                      blocks=(16, 16))
    for g, t in zip(got, twin):
        assert np.array_equal(np.asarray(g), np.asarray(t))
    assert FA._shape_sig((1, 2, 512, 16), 512, True) == \
        FA._shape_sig((1, 2, 512, 16), 512, True, 16) == \
        "b1h2sq512sk512d16c1"
    assert FA._shape_sig((1, 16, 8192, 192), 8192, True, 128) == \
        "b1h16sq8192sk8192d192v128c1"
    assert FA._bwd_vmem_limit(8192, 64, 2, 1024, 1024) == \
        FA._bwd_vmem_limit(8192, 64, 2, 1024, 1024, dv=64) == \
        8192 * 4608 + 32 * 1024 * 1024


def test_flash_tiles_label_tells_the_widths_apart():
    from paddle_tpu.observability import metrics
    q, k, v, _ = _qkv(4)
    FA.flash_attention(q, k, v, causal=True, interpret=True, blocks=(16, 16))
    FA.flash_attention(q, k, k, causal=True, interpret=True, blocks=(16, 16))
    shapes = {labels for labels in metrics.snapshot()["flash"]["tiles"]
              if "sq40sk40" in labels and "kernel=fwd" in labels}
    assert any("d24v16c1" in s for s in shapes)
    assert any("d24c1" in s for s in shapes)


def test_flash_attention_says_which_widths_it_takes():
    q, k, v, _ = _qkv(4)
    with pytest.raises(ValueError, match="k's head_dim .16. must equal q's"):
        FA.flash_attention(q, v, v, interpret=True)
    with pytest.raises(ValueError, match="must match k"):
        FA.flash_attention(q, k, v[:, :, :2], interpret=True)
    with pytest.raises(ValueError, match="multiple of"):
        FA.flash_attention(q, k[:, :, :3], v[:, :, :3], interpret=True)
