"""Operations and HBM bytes one call of the chunked gated delta rule
(KDA) needs, from its shapes: ``b`` rows of ``s`` positions, ``h``
heads, keys ``dk`` and values ``dv`` wide, chunks of ``chunk``.

Per chunk and head the forward needs the two score products over the
lower triangle (``q k^T`` and ``(b k) k^T``, each over ``dk``), the
triangular solve applied to the ``dv``-wide corrected values, the
scores times them, and three products with the [dk, dv] state (what
the state adds to the values' correction, what it adds to the output,
and its own update).  The backward needs two products for each of the
forward's.  What a kernel recomputes (the backward's scores, inverse
and corrected values), the chunk-edge states it chooses to keep, and
the exponentials are its own and are not counted.  Bytes: q, k, v and
the result in ``itemsize``, the decay's logarithm in float32 as the
model hands it over, ``beta`` one float32 a head and position."""


def _chunk_flops(chunk, dk, dv):
    scores = 2 * chunk * chunk * dk         # two products, half the tiles
    solve = chunk * chunk * dv              # forward substitution
    apply_scores = chunk * chunk * dv       # half of 2 C^2 dv
    state = 3 * 2 * chunk * dk * dv
    return float(scores + solve + apply_scores + state)


def fwd(b, h, s, dk, dv, chunk, itemsize=2):
    """Reads q, k, v, g, beta; writes o."""
    flops = b * h * (s / chunk) * _chunk_flops(chunk, dk, dv)
    nbytes = b * h * s * (itemsize * (2 * dk + 2 * dv) + 4 * dk + 4)
    return flops, nbytes


def bwd(b, h, s, dk, dv, chunk, itemsize=2):
    """Reads q, k, v, g, beta and do; writes dq, dk, dv, dg, dbeta."""
    flops = 2 * b * h * (s / chunk) * _chunk_flops(chunk, dk, dv)
    nbytes = b * h * s * (itemsize * (4 * dk + 4 * dv) + 8 * dk + 8)
    return flops, nbytes
