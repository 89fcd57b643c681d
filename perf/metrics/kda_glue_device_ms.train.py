"""``linear_attention_device_ms.train`` less the two chunk kernels
(``kda_chunk_fwd``, ``kda_chunk_bwd``): the projections, convolutions,
gates, norms and what XLA does round the kernels (the L2 normalisation,
the decay's running sums, the folding of beta, and their backward):
what a KDA layer costs beyond the scan it feeds."""
from perf import phase_reduce

SCOPE = 'linear_attention'
KERNELS = ('kda_chunk_fwd', 'kda_chunk_bwd')


def read(run):
    t = phase_reduce.table(run)
    if t is None or not t.calls:
        return None
    ns = sum(ns for cell, ns in t.cells.items()
             if SCOPE in cell.path.split("/")
             and not any(k in cell.row for k in KERNELS))
    return ns / t.calls / 1e6 if ns else None
