"""Operations and HBM bytes the held experts' SwiGLU needs for the
token slots routed to them: three grouped products a pass over
``slots`` rows in all (``h`` -> ``i`` twice, ``i`` -> ``h`` once), over
``held`` experts' weights.  The least a fused kernel would move: the
rows in, the weights, the result out; the two hidden activations need
not touch HBM.  A forward run again by recompute is the program's own
choice and is not counted."""


def fwd(slots, held, h, i, itemsize=2):
    """Reads the rows and the three weights, writes the result."""
    flops = 3 * 2.0 * slots * h * i
    nbytes = itemsize * (2.0 * slots * h + 3.0 * held * h * i)
    return flops, nbytes


def bwd(slots, held, h, i, itemsize=2):
    """Two products per forward product (the rows' gradient and the
    weights').  Reads the rows, the result's gradient and the weights;
    writes the rows' gradient and the three weight gradients."""
    flops = 2 * 3 * 2.0 * slots * h * i
    nbytes = itemsize * (3.0 * slots * h + 6.0 * held * h * i)
    return flops, nbytes
