"""What the plain references share: seeded weights, LayerNorm, GELU,
attention, AdamW, and the two lower-precision controls.

Nothing here imports the program.  Everything is ``jax.numpy`` in
float32 under ``jax.default_matmul_precision("highest")`` unless a
control asks for less: no kernel, no cache, no AMP, no recompute of the
program's (the per-layer ``jax.checkpoint`` below only bounds the
reference's own memory and changes no value).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

SEED_MASK = (1 << 63) - 1


def key_of(seed: int, stream: int = 0):
    """A PRNG key from ``--seed`` (any whole number; the driver's are
    above 2**31, so the seed is folded in as two 31-bit halves)."""
    seed = int(seed) & SEED_MASK
    key = jax.random.key(stream)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def make_weights(table, seed, low_dtype=None):
    """Every leaf of ``table`` from the seed, in one jitted call.

    ``table`` maps a leaf name to ``(shape, kind, std)``: ``normal``
    leaves are N(0, std), ``ones`` leaves 1 + N(0, std).  Leaves whose
    kind ends in ``_low`` are rounded through ``low_dtype`` where one
    is given (the type the program holds them in under AMP O2), so the
    float32 master weights of both sides start from the same values.
    Returned in float32."""
    names = sorted(table)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, kind, std = table[name]
            w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
            if kind.startswith("ones"):
                w = 1.0 + w
            if low_dtype is not None and kind.endswith("_low"):
                w = w.astype(low_dtype).astype(jnp.float32)
            out[name] = w
        return out

    return jax.jit(make)(key_of(seed, 1))


def layer_norm(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x, kind):
    """``gelu_new`` is GPT-2's tanh form, ``gelu`` the exact erf form
    the BERT release computes."""
    if kind in ("gelu_new", "gelu_tanh"):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if kind == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown activation {kind!r}")


def attention(q, k, v, causal, mm):
    """Softmax attention over [B, S, H, D] heads, scores materialised."""
    d = q.shape[-1]
    s = mm.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    if causal:
        n = q.shape[1]
        keep = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(keep, s, -jnp.inf)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
    return mm.einsum("bhqk,bkhd->bqhd", p, v)


class Matmul:
    """How a reference multiplies matrices.

    ``highest``: float32 operands, float32 accumulation, all passes.
    ``bfloat16``: every operand and every product's result rounded to
    bfloat16 (the control below a float32 configuration).
    ``fp8``: operands fake-quantised to float8_e4m3 with a per-tensor
    scale, straight-through gradients (the control below a bfloat16
    configuration)."""

    def __init__(self, mode="highest"):
        if mode not in ("highest", "bfloat16", "fp8"):
            raise ValueError(f"unknown precision {mode!r}")
        self.mode = mode

    def _prep(self, x):
        if self.mode == "bfloat16":
            return x.astype(jnp.bfloat16)
        if self.mode == "fp8":
            x = x.astype(jnp.float32)
            scale = jnp.max(jnp.abs(jax.lax.stop_gradient(x))) / 448.0
            scale = jnp.where(scale > 0, scale, 1.0)
            q = (x / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
            return x + jax.lax.stop_gradient(q - x)
        return x.astype(jnp.float32)

    def _post(self, y):
        if self.mode == "bfloat16":
            return y.astype(jnp.bfloat16).astype(jnp.float32)
        return y

    def dot(self, a, b):
        return self._post(jnp.matmul(
            self._prep(a), self._prep(b),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))

    def einsum(self, spec, a, b):
        return self._post(jnp.einsum(
            spec, self._prep(a), self._prep(b),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))

    def act(self, x):
        """Round an activation the way the mode stores it."""
        if self.mode == "bfloat16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x


def adamw(p, g, m, v, t, lr, b1, b2, eps, wd):
    """One AdamW step (Loshchilov & Hutter, decoupled decay), leaf-wise."""
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * jnp.square(g)
    m_hat = m / (1.0 - b1 ** t)
    v_hat = v / (1.0 - b2 ** t)
    p = p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + wd * p)
    return p, m, v


def leaf_norms(tree):
    """L2 norm of every leaf; a stacked leaf [L, ...] gives L norms."""
    def norm(name, x):
        x = x.astype(jnp.float32)
        if name.startswith("blocks."):
            return jnp.sqrt(jnp.sum(jnp.square(x).reshape(x.shape[0], -1),
                                    axis=1))
        return jnp.sqrt(jnp.sum(jnp.square(x)))
    return {k: norm(k, x) for k, x in tree.items()}


SKETCHES = 4


def sketch_vectors(shape, leaf_index):
    """``SKETCHES`` fixed standard-normal tensors of ``shape`` for the
    ``leaf_index``-th leaf (in sorted order of the table's names): the
    same on both sides of a comparison, whatever the seed."""
    key = jax.random.fold_in(jax.random.key(20250925), leaf_index)
    return jax.random.normal(key, (SKETCHES, *shape), jnp.float32)


def leaf_sketches(tree):
    """Per leaf, its inner products with the leaf's sketch vectors
    (elementwise in float32, no matmul unit): [SKETCHES], or
    [L, SKETCHES] for a stacked leaf.  Two gradients' sketches differ
    by about the norm of the gradients' difference, which a lower
    precision moves far more than it moves the gradient's norm."""
    out = {}
    for j, name in enumerate(sorted(tree)):
        x = tree[name].astype(jnp.float32)
        r = sketch_vectors(x.shape, j)
        axes = tuple(range(2 if name.startswith("blocks.") else 1, r.ndim))
        dots = jnp.sum(x[None] * r, axis=axes)
        out[name] = dots.T if name.startswith("blocks.") else dots
    return out


def train_three_steps(loss_rows, make, batches, opt, rows_per_block):
    """The reference's side of a training cell's check.

    ``make()`` gives the seeded weights.  ``loss_rows(params, *rows)``
    returns ``(total, parts)`` for a block of rows: each part is one
    term of the loss (token loss, sentence loss) summed over the block
    and divided by that term's count in the WHOLE batch, so that the
    blocks' values and gradients add up to the batch's.  It is
    differentiated block by block, so one block's activations are all
    that is ever held.  ``batches`` are the first steps' batches
    (tuples of arrays whose leading axis is the row); ``opt`` holds
    learning_rate, beta1, beta2, epsilon and weight_decay.

    Returns per step the loss, and per leaf the norm and the sketch of
    the first gradient and the norm of the parameters' change after
    the last step."""
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["weight_decay"]

    def grad_block(params, acc, rows):
        (_, parts), g = jax.value_and_grad(
            lambda p: loss_rows(p, *rows), has_aux=True)(params)
        return jax.tree.map(jnp.add, acc, g), parts

    grad_block = jax.jit(grad_block, donate_argnums=1)

    def apply(params, grads, m, v, t):
        out = {k: adamw(params[k], grads[k], m[k], v[k], t, lr, b1, b2,
                        eps, wd) for k in params}
        return tuple({k: o[i] for k, o in out.items()} for i in range(3))

    apply = jax.jit(apply, donate_argnums=(0, 2, 3))
    norms = jax.jit(leaf_norms)
    sketches = jax.jit(leaf_sketches)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        {k: a[k] - b[k] for k in a}))
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))

    params = make()
    m, v = zeros(params), zeros(params)
    losses, first_grad, first_sketch = [], None, None
    for step, batch in enumerate(batches, start=1):
        n = batch[0].shape[0]
        grads, total = zeros(params), 0.0
        for lo in range(0, n, rows_per_block):
            rows = tuple(a[lo:lo + rows_per_block] for a in batch)
            grads, parts = grad_block(params, grads, rows)
            total += float(sum(parts))
        losses.append(total)
        if first_grad is None:
            first_grad = jax.device_get(norms(grads))
            first_sketch = jax.device_get(sketches(grads))
        params, m, v = apply(params, grads, m, v, float(step))
        del grads
    del m, v
    change = jax.device_get(diff_norms(params, make()))
    return {"losses": losses, "first_grad_norm": first_grad,
            "first_grad_sketch": first_sketch,
            "param_change_norm": change}
