"""Loss functionals.

Analog of ``python/paddle/nn/functional/loss.py`` (reference; kernels
``paddle/phi/kernels/funcs/cross_entropy.h`` etc.). Cross-entropy follows the
reference semantics: hard or soft labels, ignore_index, class weights,
label_smoothing, use_softmax toggle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from ...core import scope as _scope
from ...core.dispatch import apply
from ...core.tensor import Tensor


def _reduce(loss, reduction, weight_sum=None):
    if reduction == "none":
        return loss
    if reduction == "sum":
        return jnp.sum(loss)
    if weight_sum is not None:
        return jnp.sum(loss) / jnp.maximum(weight_sum, 1e-12)
    return jnp.mean(loss)


@jax.custom_vjp
def _nll_fused(logits, safe):
    """Per-row -log softmax(logits)[safe]: logits [N, V], safe [N] int32
    -> [N] f32. Residuals are O(N), not O(N*V)."""
    return _nll_fwd(logits, safe)[0]


def _nll_fwd(logits, safe):
    m = jnp.max(logits, axis=1)
    s = jnp.sum(jnp.exp((logits - m[:, None]).astype(jnp.float32)),
                axis=1)
    lse = m.astype(jnp.float32) + jnp.log(s)
    picked = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
    return lse - picked.astype(jnp.float32), (logits, safe, lse)


def _nll_bwd(res, g):
    logits, safe, lse = res
    # d/dlogits = (softmax - onehot) * g, one fused pass, no residual
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == safe[:, None])
    d = (p - onehot) * g[:, None].astype(jnp.float32)
    return d.astype(logits.dtype), None


_nll_fused.defvjp(_nll_fwd, _nll_bwd)


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    args = [input, label] + ([weight] if weight is not None else [])

    def impl(logits, lab, *w):
        w = w[0] if w else None
        ax = axis if axis >= 0 else logits.ndim + axis
        n_class = logits.shape[ax]
        hard_label = not (soft_label or (
            lab.ndim == logits.ndim and lab.shape[ax] == n_class
            and jnp.issubdtype(lab.dtype, jnp.floating)))
        if (use_softmax and hard_label and w is None
                and label_smoothing == 0.0 and logits.ndim == 2
                and ax == 1):
            # fast path for the LM-loss shape ([tokens, vocab] hard
            # labels): custom-vjp NLL that saves only the [N] logsumexp
            # and recomputes softmax in the backward — the naive autodiff
            # saves a full [N, V] fp32 exp residual (1.6 GB at vocab 50k
            # and 8,192 tokens), written by the forward and read back by
            # the backward with the log-probabilities beside it.
            idx = lab
            if idx.ndim == logits.ndim:
                idx = jnp.squeeze(idx, axis=ax)
            idx = idx.astype(jnp.int32)
            valid = idx != ignore_index
            safe = jnp.where(valid, idx, 0)
            loss = jnp.where(valid, _nll_fused(logits, safe), 0.0)
            if reduction == "mean":
                n_valid = jnp.sum(valid.astype(jnp.float32))
                return jnp.sum(loss) / jnp.maximum(n_valid, 1.0)
            return _reduce(loss, reduction)
        if use_softmax:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=ax)
        else:
            logp = jnp.log(jnp.maximum(logits.astype(jnp.float32), 1e-37))
        if soft_label or (lab.ndim == logits.ndim and
                          lab.shape[ax] == n_class and
                          jnp.issubdtype(lab.dtype, jnp.floating)):
            soft = lab.astype(jnp.float32)
            if label_smoothing > 0.0:
                soft = (1 - label_smoothing) * soft + label_smoothing / n_class
            loss = -jnp.sum(soft * logp, axis=ax)
            if w is not None:
                wc = jnp.sum(soft * w.astype(jnp.float32), axis=ax)
                loss = loss * wc
                return _reduce(loss, reduction,
                               jnp.sum(wc) if reduction == "mean" else None)
            return _reduce(loss, reduction)
        idx = lab
        if idx.ndim == logits.ndim:
            idx = jnp.squeeze(idx, axis=ax)
        idx = idx.astype(jnp.int32)
        valid = idx != ignore_index
        safe = jnp.where(valid, idx, 0)
        if label_smoothing > 0.0:
            nll = -jnp.take_along_axis(
                logp, safe[..., None] if ax == logits.ndim - 1
                else jnp.expand_dims(safe, ax), axis=ax).squeeze(ax)
            smooth = -jnp.mean(logp, axis=ax)
            loss = (1 - label_smoothing) * nll + label_smoothing * smooth
        else:
            loss = -jnp.take_along_axis(
                logp, jnp.expand_dims(safe, ax), axis=ax).squeeze(ax)
        loss = jnp.where(valid, loss, 0.0)
        if w is not None:
            wc = jnp.where(valid, jnp.take(w.astype(jnp.float32), safe), 0.0)
            loss = loss * wc
            return _reduce(loss, reduction,
                           jnp.sum(wc) if reduction == "mean" else None)
        if reduction == "mean":
            n_valid = jnp.sum(valid.astype(jnp.float32))
            return jnp.sum(loss) / jnp.maximum(n_valid, 1.0)
        return _reduce(loss, reduction)

    with _scope.phase("loss"):
        return apply("cross_entropy", impl, *args)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    from .activation import softmax as _softmax
    from ... import ops
    loss = ops.unsqueeze(loss, axis)
    if return_softmax:
        return loss, _softmax(logits, axis=axis)
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    args = [input, label] + ([weight] if weight is not None else [])

    def impl(logp, lab, *w):
        w = w[0] if w else None
        idx = lab.astype(jnp.int32)
        valid = idx != ignore_index
        safe = jnp.where(valid, idx, 0)
        loss = -jnp.take_along_axis(
            logp, jnp.expand_dims(safe, 1), axis=1).squeeze(1)
        loss = jnp.where(valid, loss, 0.0)
        if w is not None:
            wc = jnp.where(valid, jnp.take(w, safe), 0.0)
            loss = loss * wc
            return _reduce(loss, reduction,
                           jnp.sum(wc) if reduction == "mean" else None)
        if reduction == "mean":
            n_valid = jnp.sum(valid.astype(jnp.float32))
            return jnp.sum(loss) / jnp.maximum(n_valid, 1.0)
        return _reduce(loss, reduction)

    return apply("nll_loss", impl, *args)


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss",
                 lambda a, b: _reduce(jnp.square(a - b), reduction),
                 input, label)


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss",
                 lambda a, b: _reduce(jnp.abs(a - b), reduction),
                 input, label)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def impl(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        # paddle multiplies by delta (huber parametrization)
        return _reduce(loss * delta, reduction)

    return apply("smooth_l1_loss", impl, input, label)


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    args = [input, label] + ([weight] if weight is not None else [])

    def impl(p, y, *w):
        p32 = jnp.clip(p.astype(jnp.float32), 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p32) + (1 - y) * jnp.log1p(-p32))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)

    return apply("binary_cross_entropy", impl, *args)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    args = [logit, label]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        args.append(weight)
    if has_pw:
        args.append(pos_weight)

    def impl(z, y, *rest):
        z32 = z.astype(jnp.float32)
        y32 = y.astype(jnp.float32)
        i = 0
        w = rest[i] if has_w else None
        if has_w:
            i += 1
        pw = rest[i] if has_pw else None
        # stable: max(z,0) - z*y + log(1+exp(-|z|)), pos_weight scales +term
        log1pexp = jnp.logaddexp(0.0, -jnp.abs(z32))
        if pw is not None:
            coeff = (pw - 1.0) * y32 + 1.0
            loss = (1 - y32) * z32 + coeff * (
                jnp.logaddexp(0.0, -z32))
        else:
            loss = jnp.maximum(z32, 0) - z32 * y32 + log1pexp
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    return apply("bce_with_logits", impl, *args)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def impl(logp, y):
        if log_target:
            loss = jnp.exp(y) * (y - logp)
        else:
            y32 = y.astype(jnp.float32)
            loss = jnp.where(y32 > 0, y32 * (jnp.log(jnp.maximum(y32, 1e-37))
                                             - logp), 0.0)
        if reduction == "batchmean":
            return jnp.sum(loss) / loss.shape[0]
        return _reduce(loss, reduction)

    return apply("kl_div", impl, input, label)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    def impl(a, b, y):
        loss = jnp.maximum(-y * (a - b) + margin, 0.0)
        return _reduce(loss, reduction)

    return apply("margin_ranking_loss", impl, input, other, label)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    def impl(x, y):
        loss = jnp.where(y == 1.0, x, jnp.maximum(0.0, margin - x))
        return _reduce(loss, reduction)

    return apply("hinge_embedding_loss", impl, input, label)


def cosine_embedding_loss(input1, input2, label, margin=0.0,
                          reduction="mean", name=None):
    def impl(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)

    return apply("cosine_embedding_loss", impl, input1, input2, label)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    def impl(a, pos, neg):
        d_ap = jnp.power(jnp.sum(jnp.power(jnp.abs(a - pos) + epsilon, p),
                                 axis=-1), 1.0 / p)
        d_an = jnp.power(jnp.sum(jnp.power(jnp.abs(a - neg) + epsilon, p),
                                 axis=-1), 1.0 / p)
        if swap:
            d_pn = jnp.power(jnp.sum(jnp.power(jnp.abs(pos - neg) + epsilon,
                                               p), axis=-1), 1.0 / p)
            d_an = jnp.minimum(d_an, d_pn)
        loss = jnp.maximum(d_ap - d_an + margin, 0.0)
        return _reduce(loss, reduction)

    return apply("triplet_margin_loss", impl, input, positive, negative)


def square_error_cost(input, label):
    return apply("square_error_cost", lambda a, b: jnp.square(a - b),
                 input, label)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    args = [logit, label] + ([normalizer] if normalizer is not None else [])

    def impl(z, y, *n):
        z32, y32 = z.astype(jnp.float32), y.astype(jnp.float32)
        p = jax.nn.sigmoid(z32)
        ce = jnp.maximum(z32, 0) - z32 * y32 + jnp.logaddexp(0.0, -jnp.abs(z32))
        p_t = p * y32 + (1 - p) * (1 - y32)
        a_t = alpha * y32 + (1 - alpha) * (1 - y32)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)

    return apply("sigmoid_focal_loss", impl, *args)


def log_loss(input, label, epsilon=1e-4, name=None):
    def impl(p, y):
        p32 = p.astype(jnp.float32)
        return -(y * jnp.log(p32 + epsilon) +
                 (1 - y) * jnp.log(1 - p32 + epsilon))

    return apply("log_loss", impl, input, label)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via the standard dynamic program in log space (lax.scan over
    time). Reference: warpctc binding (``paddle/phi/kernels/gpu/
    warpctc_kernel.cu``); here it's pure XLA so it runs on TPU."""
    args = [log_probs, labels, input_lengths, label_lengths]

    def impl(lp, lab, in_len, lab_len):
        # lp: [T, B, C] logits (paddle convention); normalize to log-probs
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        T, B, C = lp.shape
        S = lab.shape[1]
        ext = 2 * S + 1
        NEG = -1e30
        # extended label seq: blank l1 blank l2 ... blank
        ext_lab = jnp.full((B, ext), blank, dtype=jnp.int32)
        ext_lab = ext_lab.at[:, 1::2].set(lab.astype(jnp.int32))
        same_as_prev2 = jnp.concatenate(
            [jnp.zeros((B, 2), bool),
             ext_lab[:, 2:] == ext_lab[:, :-2]], axis=1)
        is_blank = ext_lab == blank

        alpha0 = jnp.full((B, ext), NEG)
        alpha0 = alpha0.at[:, 0].set(lp[0, :, blank])
        alpha0 = alpha0.at[:, 1].set(
            jnp.take_along_axis(lp[0], ext_lab[:, 1:2], axis=1)[:, 0])

        def step(alpha, lp_t):
            shift1 = jnp.concatenate(
                [jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
            shift2 = jnp.concatenate(
                [jnp.full((B, 2), NEG), alpha[:, :-2]], axis=1)
            allow2 = (~is_blank) & (~same_as_prev2)
            merged = jnp.logaddexp(alpha, shift1)
            merged = jnp.where(allow2, jnp.logaddexp(merged, shift2), merged)
            emit = jnp.take_along_axis(lp_t, ext_lab, axis=1)
            return merged + emit, merged + emit

        _, alphas = jax.lax.scan(step, alpha0, lp[1:])
        alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # [T,B,ext]
        t_idx = (in_len.astype(jnp.int32) - 1)
        last = jnp.take_along_axis(
            alphas, t_idx[None, :, None].repeat(ext, 2), axis=0)[0]
        end1 = 2 * lab_len.astype(jnp.int32)      # final blank
        end2 = 2 * lab_len.astype(jnp.int32) - 1  # final label
        ll = jnp.logaddexp(
            jnp.take_along_axis(last, end1[:, None], axis=1)[:, 0],
            jnp.take_along_axis(last, jnp.maximum(end2, 0)[:, None],
                                axis=1)[:, 0])
        loss = -ll
        if norm_by_times:
            loss = loss / in_len.astype(jnp.float32)
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(
                lab_len.astype(jnp.float32), 1.0))
        return _reduce(loss, reduction)

    return apply("ctc_loss", impl, *args)


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):
    """Reference ``huber_loss`` op: 0.5 r^2 inside |r| <= delta, linear
    outside (the unscaled Huber — ``smooth_l1_loss`` is paddle's
    delta-scaled variant)."""
    def impl(a, b):
        r = jnp.abs(a - b)
        loss = jnp.where(r <= delta, 0.5 * r * r,
                         delta * (r - 0.5 * delta))
        return _reduce(loss, reduction)

    return apply("huber_loss", impl, input, label)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Reference ``hsigmoid_loss``: hierarchical sigmoid over a binary
    tree; returns the per-sample loss [N, 1] (reference output shape).
    Default tree = complete binary heap (leaf of class c at heap slot
    c + num_classes - 1, internal nodes 0..num_classes-2), computed with
    traceable bit arithmetic so the loss works under jit; custom trees
    come via ``path_table``/``path_code`` [N, L] (padded with -1)."""
    import numpy as np

    from ...core.dispatch import unwrap

    n = int(num_classes)
    depth = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    use_default_tree = path_table is None
    if not use_default_tree:
        path_table = np.asarray(unwrap(path_table), np.int32)
        path_code = np.asarray(unwrap(path_code), np.float32)

    def impl(x, lab, w, *maybe_bias):
        if use_default_tree:
            # walk the heap from each label's leaf up — fixed `depth`
            # unrolled steps, pure jnp (jit-traceable)
            node = lab.reshape(-1).astype(jnp.int32) + n - 1
            steps = []
            for _ in range(depth):
                parent = (node - 1) // 2
                steps.append((parent, (node == 2 * parent + 2), node > 0))
                node = parent
            pt = jnp.stack([s[0] for s in steps[::-1]], axis=1)
            pc = jnp.stack([s[1] for s in steps[::-1]],
                           axis=1).astype(x.dtype)
            vmask = jnp.stack([s[2] for s in steps[::-1]],
                              axis=1).astype(x.dtype)
        else:
            pt = jnp.asarray(path_table)
            pc = jnp.asarray(path_code)
            vmask = (pt >= 0).astype(x.dtype)
        idx = jnp.maximum(pt, 0)
        wn = jnp.take(w, idx, axis=0)             # [N, L, D]
        logits = jnp.einsum("nd,nld->nl", x, wn)
        if maybe_bias:
            logits = logits + jnp.take(maybe_bias[0].reshape(-1), idx)
        # sigmoid CE with target = code (1 right, 0 left)
        ce = jnp.maximum(logits, 0) - logits * pc + jnp.log1p(
            jnp.exp(-jnp.abs(logits)))
        return jnp.sum(ce * vmask, axis=1, keepdims=True)  # [N, 1]

    args = (input, label, weight) + ((bias,) if bias is not None else ())
    return apply("hsigmoid_loss", impl, *args)


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.0, reduction="mean", name=None):
    """Reference ``warprnnt`` op (``rnnt_loss``): RNN-Transducer negative
    log-likelihood over logits [B, T, U+1, V] and labels [B, U] —
    log-domain forward DP as a scan over time (the TPU-shaped replacement
    for the warp-rnnt CUDA kernel)."""
    if fastemit_lambda:
        raise NotImplementedError(
            "rnnt_loss: FastEmit regularization is not implemented; "
            "pass fastemit_lambda=0")

    def impl(logits, labels, in_len, lab_len):
        B, T, U1, V = logits.shape
        U = U1 - 1
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp_blank = lp[..., blank]                      # [B, T, U+1]
        lab = labels.astype(jnp.int32)                 # [B, U]
        # emit log-prob at (t, u): P(label_u | t, u), u < U
        lp_emit = jnp.take_along_axis(
            lp[:, :, :U, :], lab[:, None, :, None], axis=-1)[..., 0]
        NEG = jnp.float32(-1e30)

        def emit_at(t, u_minus_1):
            # lp_emit[:, t, max(u-1, 0)] without dynamic gather per batch
            return jnp.take_along_axis(
                lp_emit[:, t, :],
                jnp.broadcast_to(jnp.maximum(u_minus_1, 0), (B, 1)),
                axis=1)[:, 0]

        def row(from_blank, t):
            """alpha row at time t given the blank-moves column
            from_blank[u]; vertical emit recurrence is sequential in u."""
            def scan_u(carry, u):
                a = jnp.where(u == 0, from_blank[:, 0],
                              jnp.logaddexp(from_blank[:, u],
                                            carry + emit_at(t, u - 1)))
                return a, a

            _, cols = lax.scan(scan_u, jnp.full((B,), NEG),
                               jnp.arange(U1))
            return jnp.swapaxes(cols, 0, 1)

        # t = 0: no blank moves; alpha[0,0] = 0, alpha[0,u] pure emits
        def scan_u0(carry, u):
            a = jnp.where(u == 0, 0.0, carry + emit_at(0, u - 1))
            return a, a

        _, cols0 = lax.scan(scan_u0, jnp.zeros((B,)), jnp.arange(U1))
        alpha0 = jnp.swapaxes(cols0, 0, 1)

        def full_step(alpha, t):
            new = row(alpha + lp_blank[:, t - 1, :], t)
            return new, new

        _, rows = lax.scan(full_step, alpha0, jnp.arange(1, T))
        alphas = jnp.concatenate([alpha0[None], rows], axis=0)  # [T,B,U1]
        t_last = (in_len.astype(jnp.int32) - 1)
        last = jnp.take_along_axis(
            alphas, t_last[None, :, None].repeat(U1, 2), axis=0)[0]
        a_end = jnp.take_along_axis(
            last, lab_len.astype(jnp.int32)[:, None], axis=1)[:, 0]
        blank_end = jnp.take_along_axis(
            jnp.take_along_axis(
                lp_blank, t_last[:, None, None].repeat(U1, 2),
                axis=1)[:, 0, :],
            lab_len.astype(jnp.int32)[:, None], axis=1)[:, 0]
        loss = -(a_end + blank_end)
        return _reduce(loss, reduction)

    return apply("rnnt_loss", impl, input, label, input_lengths,
                 label_lengths)


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean",
                         name=None):
    """Reference ``margin_cross_entropy`` (ArcFace/CosFace family):
    target logit cos(theta) -> cos(m1*theta + m2) - m3, all scaled by
    ``scale``, then softmax CE. Single-program form — under TP the vocab
    dim shards via GSPMD instead of the reference's c_softmax collective
    (``group`` accepted for signature parity)."""
    def impl(lg, y):
        yy = y.reshape(-1).astype(jnp.int32)
        cos_t = jnp.take_along_axis(lg, yy[:, None], axis=1)[:, 0]
        # stay strictly inside (-1, 1): arccos' derivative is -inf at
        # the boundary and a perfectly-aligned feature would NaN the step
        cos_t = jnp.clip(cos_t, -1.0 + 1e-6, 1.0 - 1e-6)
        theta = jnp.arccos(cos_t)
        target = jnp.cos(margin1 * theta + margin2) - margin3
        adj = lg.at[jnp.arange(lg.shape[0]), yy].set(target) * scale
        logp = jax.nn.log_softmax(adj, axis=-1)
        loss = -jnp.take_along_axis(logp, yy[:, None], axis=1)
        sm = jnp.exp(logp)
        if reduction == "mean":
            loss_out = jnp.mean(loss)
        elif reduction == "sum":
            loss_out = jnp.sum(loss)
        else:
            loss_out = loss
        return (loss_out, sm) if return_softmax else loss_out

    return apply("margin_cross_entropy", impl, logits, label)


def class_center_sample(label, num_classes, num_samples, group=None):
    """Reference ``class_center_sample``: keep the batch's positive
    classes plus random negatives up to ``num_samples`` unique centers;
    returns (remapped_label, sampled_class_indices). Host-side sampling
    (data-dependent sizes), seeded by the framework RNG."""
    import numpy as np

    from ...core import state
    from ...core.dispatch import unwrap
    from ...core.tensor import Tensor

    if num_samples > num_classes:
        raise ValueError(f"class_center_sample: num_samples "
                         f"{num_samples} > num_classes {num_classes}")
    y = np.asarray(unwrap(label)).reshape(-1)
    pos = np.unique(y)
    import jax as _jax
    key = np.asarray(_jax.random.key_data(state.default_rng.next_key()))
    rng = np.random.default_rng(key.astype(np.uint32))
    if len(pos) >= num_samples:
        sampled = pos
    else:
        neg_pool = np.setdiff1d(np.arange(num_classes), pos)
        extra = rng.choice(neg_pool, size=num_samples - len(pos),
                           replace=False)
        sampled = np.sort(np.concatenate([pos, extra]))
    remap = -np.ones(num_classes, np.int64)
    remap[sampled] = np.arange(len(sampled))
    return (Tensor(remap[y].astype(np.int64)),
            Tensor(sampled.astype(np.int64)))
