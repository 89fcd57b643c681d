"""Operations and HBM bytes one flash-attention call needs, from its
shapes.  Causal attention needs half the score tiles.  The backward
needs four products (dV, dP, dQ, dK); its fifth, the recomputation of
the scores, is the kernel's own choice and is not counted."""


def fwd(b, h, sq, sk, d, causal, itemsize=2):
    """QK^T and PV: 2 products of 2*sq*sk*d multiply-adds' flops each.
    Reads q, k, v; writes o and the float32 row statistics."""
    share = 0.5 if causal else 1.0
    flops = 2 * 2.0 * b * h * sq * sk * d * share
    nbytes = itemsize * b * h * d * (2 * sq + 2 * sk) + 4 * b * h * sq
    return flops, nbytes


def bwd(b, h, sq, sk, d, causal, itemsize=2):
    """Reads q, k, v, o, do and the row statistics; writes dq, dk, dv."""
    share = 0.5 if causal else 1.0
    flops = 4 * 2.0 * b * h * sq * sk * d * share
    nbytes = itemsize * b * h * d * (4 * sq + 4 * sk) + 4 * b * h * sq
    return flops, nbytes
