"""Element-mode N:M sparse x dense matmul — Pallas TPU kernel.

Semantics: ``out = act @ unpack(vals, idx)`` where the weight matrix is
stored in the compact N:M format (values K*N/M of dense, uint8 group
offsets), pattern chosen independently per output column — the paper's
faithful sparsity granularity.

TPU adaptation (see DESIGN.md §2): the MXU cannot skip individual MACs,
so the win here is *memory*: HBM->VMEM weight traffic is N/M of dense
(+1 byte/val of index), which is the dominant term in decode/serving and
in the BP pass of training.  Each grid step:

  1. streams a compact (TKc, TF) value tile + its offsets into VMEM,
  2. decompresses to a dense (TK, TF) tile entirely in VMEM
     (M-way select against the offset plane — no gather needed).  The
     group members of compact row g*N+j sit N rows apart, and dense row
     g*M+s M rows apart, so both sides move through 32-bit VMEM scratch
     with sublane-strided loads and stores (Mosaic's strided access needs
     32-bit data in a 128-lane memref: TF is 128),
  3. feeds the MXU a dense (TB, TK) x (TK, TF) partial matmul,
  4. accumulates over the K grid axis in an fp32 VMEM tile.

The decompression is O(TK*TF) vector work vs O(TB*TK*TF) MXU work, so it
pipelines away for TB >= 8 (one sublane quantum).

WS/OS note: this grid order keeps the *output* tile stationary in VMEM
across the contraction axis (OS dataflow); the weight tile is re-streamed
— the right choice when weights are compact (small) and outputs are fp32
(large).  The paper's WS mode corresponds to swapping the grid so the
decompressed weight tile persists; XLA's emitted loop structure makes OS
the profitable one on TPU, which we record as a dataflow adaptation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _spmm_kernel(act_ref, vals_ref, idx_ref, out_ref, vt, it, wt, *, n: int,
                 m: int, idx_bits: int = 8):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    kc = vals_ref.shape[0]
    g = kc // n
    vt[...] = vals_ref[...].astype(jnp.float32)
    idx = idx_ref[...].astype(jnp.int32)
    if idx_bits == 4:
        # nibble expansion inside the tile: byte r holds compact rows 2r
        # (low nibble) and 2r+1 — the byte-wide index never hits HBM
        half = idx.shape[0]
        it[pl.ds(0, half, stride=2), :] = idx & 0xF
        it[pl.ds(1, half, stride=2), :] = idx >> 4
    else:
        it[...] = idx
    v = [vt[pl.ds(j, g, stride=n), :] for j in range(n)]
    i = [it[pl.ds(j, g, stride=n), :] for j in range(n)]
    for s in range(m):
        d = jnp.where(i[0] == s, v[0], 0.0)
        for vj, ij in zip(v[1:], i[1:]):
            d = d + jnp.where(ij == s, vj, 0.0)
        wt[pl.ds(s, g, stride=m), :] = d
    out_ref[...] += jnp.dot(
        act_ref[...],
        wt[...].astype(act_ref.dtype),
        preferred_element_type=jnp.float32,
    )


def nm_spmm_pallas(
    act: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    n: int,
    m: int,
    *,
    block_b: int = 128,
    block_f: int = 128,
    block_k: int = 512,
    idx_bits: int = 8,
    interpret: bool = False,
):
    """act (B, K) @ packed weights (Kc=K*n/m, F) -> (B, F) fp32.

    ``idx_bits=4`` consumes the u4-packed index plane (ceil(Kc/2), F):
    the index BlockSpec streams half the bytes per tile and the nibble
    expansion is fused into the tile decompress, so decode moves
    ``Kc*F`` value bytes + ``Kc*F/2`` index bytes and nothing dense.
    With more than one K tile the per-tile compact length must be even
    (a byte never straddles two tiles).
    """
    b, k = act.shape
    kc, f = vals.shape
    assert kc * m == k * n, (k, kc, n, m)
    block_b = min(block_b, b)
    block_f = min(block_f, f)
    block_k = min(block_k, k)
    assert b % block_b == 0 and f % block_f == 0 and k % block_k == 0
    assert block_k % m == 0
    block_kc = block_k // m * n
    if idx_bits == 4:
        assert block_kc % 2 == 0 or block_k == k, (
            f"u4 tiles must be even, got block_kc={block_kc}")
        assert idx.shape == ((kc + 1) // 2, f), (idx.shape, kc, f)
        block_kci = (block_kc + 1) // 2
    else:
        assert idx.shape == vals.shape
        block_kci = block_kc
    grid = (b // block_b, f // block_f, k // block_k)
    return pl.pallas_call(
        functools.partial(_spmm_kernel, n=n, m=m, idx_bits=idx_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_b, block_k),
                lambda i, j, kk: (i, kk),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (block_kc, block_f),
                lambda i, j, kk: (kk, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (block_kci, block_f),
                lambda i, j, kk: (kk, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_b, block_f),
            lambda i, j, kk: (i, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b, f), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block_kc, block_f), jnp.float32),
            pltpu.VMEM((2 * block_kci if idx_bits == 4 else block_kc,
                        block_f), jnp.int32),
            pltpu.VMEM((block_k, block_f), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.ARBITRARY,
            )
        ),
        interpret=interpret,
        name=f"nm_spmm_{n}_{m}" + ("_u4" if idx_bits == 4 else ""),
    )(act, vals, idx)
