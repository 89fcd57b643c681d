"""Operations and HBM bytes one sliding-window flash-attention call
needs, from its shapes: ``b`` rows of ``s`` positions, ``h`` query
heads over ``kv`` key/value heads ``d`` wide, a causal ``window`` of
keys (query ``i`` sees ``i - window < j <= i``).

The score pairs are counted exactly: the band, not the tiles a kernel
visits.  Forward two products over the pairs (``q k^T``, ``p v``),
backward four (dV, dP, dQ, dK); the backward's recomputation of the
scores is the kernel's own choice and is not counted.  Bytes as
``kernel_costs/flash_attention``: every tensor once, whatever the band
makes a kernel read twice."""


def pairs(s, window):
    """Score pairs a row of ``s`` positions needs: position ``i`` sees
    ``min(i + 1, window)`` keys.  A window no shorter than the row gives
    the causal triangle, diagonal included."""
    w = min(window, s)
    return w * s - w * (w - 1) // 2


def fwd(b, h, kv, s, d, window, itemsize=2):
    """Reads q, k, v; writes o and the float32 row statistics."""
    flops = 2 * 2.0 * b * h * d * pairs(s, window)
    nbytes = itemsize * b * d * s * (2 * h + 2 * kv) + 4 * b * h * s
    return flops, nbytes


def bwd(b, h, kv, s, d, window, itemsize=2):
    """Reads q, k, v, o, do and the row statistics; writes dq, dk, dv."""
    flops = 4 * 2.0 * b * h * d * pairs(s, window)
    nbytes = itemsize * b * d * s * (4 * h + 4 * kv) + 4 * b * h * s
    return flops, nbytes
