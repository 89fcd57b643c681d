"""TPU Pallas fused-kernel library.

Capability analog of the reference's hand-written CUDA fusion tier
(SURVEY C12/C13: ``paddle/phi/kernels/fusion/gpu/`` and the FlashAttention-2
integration ``paddle/phi/kernels/gpu/flash_attn_kernel.cu:91``) — but
implemented as Mosaic/Pallas TPU kernels: online-softmax flash attention
tiled for the MXU, fused norm kernels that keep stats in VMEM, and a fused
rotary-embedding kernel.

Off-TPU (CPU CI, the 8-device virtual mesh) every kernel transparently runs
in Pallas interpreter mode, so the exact same code path is testable without
hardware — the analog of the reference's fake_cpu_device plugin fixture
(SURVEY §4).
"""
from __future__ import annotations

import jax


def use_interpret() -> bool:
    """Pallas kernels compile only for real TPUs; elsewhere interpret."""
    return jax.default_backend() != "tpu"


def out_struct(shape, dtype, *operands) -> jax.ShapeDtypeStruct:
    """``out_shape`` entry of a ``pallas_call`` that a ``jax.shard_map``
    body may reach: under ``check_vma`` the output has to name the mesh
    axes it varies over — those its operands vary over (none outside a
    shard_map)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


#: Serving options whose kernels the TPU's compiler refuses today, with
#: its words.  ``tests/test_chip_compile.py`` holds every refusal as a
#: strict xfail: repairing a kernel flips its case, and its entry here
#: goes in the same change.  The engine raises ``UnimplementedError``
#: for a listed option on a TPU instead of failing inside a compile.
TPU_REFUSED = {
    "kv_quant": (
        "the int8 KV pools' scale side-pools [Hk, P, page_size] are "
        "windowed one page at a time, and the Pallas TPU lowering "
        "requires the last two dimensions of a block to be divisible "
        "by 8 and 128 or equal to the array's: block (1, page_size) "
        "is neither"),
}


from . import flash_attention  # noqa: E402
from . import fused_optimizer  # noqa: E402
from . import fused_residual_norm  # noqa: E402
from . import kda  # noqa: E402
from . import norms  # noqa: E402
from . import rope  # noqa: E402

__all__ = ["flash_attention", "fused_optimizer", "fused_residual_norm",
           "kda", "norms", "rope", "out_struct", "use_interpret", "TPU_REFUSED"]
