"""Device time per step of the two chunk kernels of the gated delta
rule (``kda_chunk_fwd``, ``kda_chunk_bwd``: ``ops/pallas/kda.py``),
every call of every KDA layer."""
from perf import phase_reduce

KERNELS = ('kda_chunk_fwd', 'kda_chunk_bwd')


def read(run):
    t = phase_reduce.table(run)
    if t is None or not t.calls:
        return None
    ns = sum(ns for cell, ns in t.cells.items()
             if any(k in cell.row for k in KERNELS))
    return ns / t.calls / 1e6 if ns else None
