"""Custom-kernel package: Pallas TPU kernels + jnp oracles.

This is the public kernel surface — consumers (core/operand, serve,
benchmarks) import from here instead of deep-importing the private
modules:

  nm_compact(w, n, m, *, idx_bits=8)
      SORE compact packing: dense -> (vals, idx) along the second-to-
      last axis.  ``idx_bits=4`` emits the half-width index plane (two
      in-group offsets per byte, low nibble first, final high nibble
      zero-padded on odd compact extents; requires M <= 16).
  nm_spmm(x, vals, idx, n, m, *, idx_bits=8)
      fused decompress-matmul: the dense weight tile exists only in
      VMEM.  ``idx_bits=4`` expands nibbles inside the kernel tile, so
      the index plane crosses HBM at half width.  The two widths are
      bitwise interchangeable by construction and pinned so in
      tests/test_operand.py.
  nm_spmm_shared / fused_update
      reduced-K shared-pattern matmul; fused SGD + re-sparsify weight
      update (emits u8 or u4 planes to match the operand).
  nm_compact_pallas / nm_spmm_pallas / nm_spmm_shared_pallas /
  fused_update_pallas
      the raw pallas_call wrappers behind the jit'd dispatchers above
      — compiled by Mosaic on TPU, interpret mode elsewhere, the oracle
      with ``use_pallas=False``.
  decompress_nm(vals, idx, n, m, *, idx_bits=8)
      the one XLA (vals, idx) -> dense N:M expansion (select-based,
      scatter-free) used by the oracle and the operand's jnp backend;
      unpacks u4 nibbles first when ``idx_bits=4``.
  pack_shared / packed_bytes
      host-side shared-mode packer + HBM byte accounting.
"""

from repro.kernels.fused_update import fused_update_pallas
from repro.kernels.nm_compact import nm_compact_pallas
from repro.kernels.nm_spmm import nm_spmm_pallas
from repro.kernels.nm_spmm_shared import decompress_nm, nm_spmm_shared_pallas
from repro.kernels.ops import (fused_update, nm_compact, nm_spmm,
                               nm_spmm_shared, pack_shared, packed_bytes)

__all__ = [
    "nm_compact", "nm_spmm", "nm_spmm_shared", "fused_update",
    "nm_compact_pallas", "nm_spmm_pallas", "nm_spmm_shared_pallas",
    "fused_update_pallas", "decompress_nm", "pack_shared", "packed_bytes",
]
