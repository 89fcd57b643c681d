"""HBM bytes one ragged paged-attention call needs: the keys and values
actually resident for the slots in the call (it is bound by bandwidth
in decode: a query row against thousands of cached rows)."""


def call_bytes(kv_tokens, kv_heads, head_dim, q_tokens, q_heads,
               itemsize=4):
    """Reads k and v of every resident token once, reads q and writes
    o for the call's query tokens."""
    kv = 2 * kv_tokens * kv_heads * head_dim * itemsize
    qo = 2 * q_tokens * q_heads * head_dim * itemsize
    return kv + qo

