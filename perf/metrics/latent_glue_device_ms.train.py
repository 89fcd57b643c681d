"""``latent_attention_device_ms.train`` less the two flash kernels
(``flash_attention_fwd``, ``flash_attention_bwd``): the projections, the
latent's norm, the rotation, the assembly and the layout copies round
the kernels: what latent attention costs beyond the attention it
feeds."""
from perf import phase_reduce

SCOPE = 'latent_attention'
KERNELS = ('flash_attention_fwd', 'flash_attention_bwd')


def read(run):
    t = phase_reduce.table(run)
    if t is None or not t.calls:
        return None
    ns = sum(ns for cell, ns in t.cells.items()
             if SCOPE in cell.path.split("/")
             and not any(k in cell.row for k in KERNELS))
    return ns / t.calls / 1e6 if ns else None
