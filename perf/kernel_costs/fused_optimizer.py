"""HBM bytes one fused AdamW step needs: it is bound by bandwidth (a
few operations an element), so only bytes are counted."""


def step_bytes(n_low, n_float32, low_itemsize=2):
    """Per low-precision parameter with a float32 master: read grad
    (low), master, m, v; write master, m, v and the low copy.  Per
    float32 parameter: read grad, p, m, v; write p, m, v."""
    low = n_low * (2 * low_itemsize + 6 * 4)
    full = n_float32 * 7 * 4
    return low + full
