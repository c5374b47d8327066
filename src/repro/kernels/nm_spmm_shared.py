"""Shared-pattern N:M reduced-K matmul — the MXU-native FLOP-saving mode.

Beyond-paper TPU adaptation (DESIGN.md §2): when the N:M survivor pattern
is shared across a 128-wide tile of output columns, the contraction axis
itself can be *gathered and shortened*: instead of decompressing weights
to dense K, we gather the N/M surviving activation columns once per
output tile and contract over Kc = K*N/M.  The MXU then executes N/M of
the dense FLOPs — this recovers on a rigid systolic array the compute
saving that the paper's value-serial USPE achieves per-element on FPGA.

Layout:
  act : (B, K) dense
  vals: (nf, Kc, TF)  per-output-tile packed weights
  rows: (nf, Kc) int32 absolute K indices of the survivors (ascending)
  out : (B, nf*TF) fp32

Grid is (B tiles, F tiles); the full K panel of activations for a B
tile is held in VMEM, transposed and in f32 (bounded by ops.py, which
raises when it would not fit), and the survivors' rows are gathered by
index from SMEM into a (Kc, TB) panel that the MXU contracts against
the tile's packed weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# Shared decompress helper — THE (vals, idx) -> dense expansion
# ---------------------------------------------------------------------------
#
# One XLA implementation of the element-mode N:M decompression, used by
# the ref.py oracle and the core/operand jnp backend (the nm_spmm kernel
# computes the same selects plane by plane in VMEM, tests/test_operand
# pins the two bitwise).  Select-based (an M-way select against the
# offset plane), so it lowers scatter-free — O(K*F) vector work that
# pipelines away against the MXU matmul.  Exact: packed values are kept
# verbatim and every in-group offset hits exactly one slot, so the
# result is bitwise-identical to the scatter formulation
# (core/sparsity.nm_unpack_n).


def unpack_idx_nibbles(idx: jax.Array, kc: int, axis: int) -> jax.Array:
    """Two-per-byte nibble expansion along ``axis`` (low nibble first).

    Kernel-safe inline of ``core.sparsity.unpack_idx_u4`` — interleaves
    ``idx & 0xF`` and ``idx >> 4`` and trims to ``kc`` entries.  Lives
    here so the Pallas tile decompress never imports the core layer.
    """
    axis = axis % idx.ndim
    lo = idx & jnp.uint8(0x0F)
    hi = idx >> 4
    pair = jnp.stack([lo, hi], axis=axis + 1)
    shape = idx.shape[:axis] + (2 * idx.shape[axis],) + idx.shape[axis + 1:]
    return jax.lax.slice_in_dim(pair.reshape(shape), 0, kc, axis=axis)


def decompress_nm(vals: jax.Array, idx: jax.Array, n: int, m: int,
                  axis: int = -1, idx_bits: int = 8) -> jax.Array:
    """(…, Kc, …) packed -> (…, K, …) dense along ``axis``, K = Kc*m/n.

    dense[g*m + s] = sum_j vals[g*n + j] * (idx[g*n + j] == s), unrolled
    over the m slot positions — all ops are selects/adds, no scatter.

    ``idx_bits=4`` accepts the u4-packed index plane (two in-group
    offsets per byte along ``axis``, ceil(Kc/2) bytes); it is expanded
    with :func:`unpack_idx_nibbles` first, so the result is bitwise
    identical to the byte-wide path on the same offsets.
    """
    axis = axis % vals.ndim
    kc = vals.shape[axis]
    if kc % n:
        raise ValueError(f"packed axis {kc} not divisible by n={n}")
    if idx_bits == 4:
        idx = unpack_idx_nibbles(idx, kc, axis)
    elif idx_bits != 8:
        raise ValueError(f"idx_bits must be 4 or 8, got {idx_bits}")
    shape = vals.shape
    g = kc // n
    gshape = shape[:axis] + (g, n) + shape[axis + 1:]
    v = vals.reshape(gshape)
    i = idx.reshape(gshape)
    slots = []
    for s in range(m):
        hit = (i == s)
        slots.append(jnp.sum(jnp.where(hit, v, 0), axis=axis + 1))
    dense = jnp.stack(slots, axis=axis + 1)  # (…, G, M, …)
    return dense.reshape(shape[:axis] + (g * m,) + shape[axis + 1:])


def _spmm_shared_kernel(rows_ref, act_t_ref, vals_ref, out_ref, gt, *,
                        act_dtype):
    # gather the Kc surviving activation rows of this F tile from the
    # transposed (K, TB) panel: one dynamic-offset row copy per survivor
    # (32-bit rows — Mosaic has no lane gather)
    def copy_row(c, carry):
        gt[pl.ds(c, 1), :] = act_t_ref[pl.ds(rows_ref[0, 0, c], 1), :]
        return carry

    jax.lax.fori_loop(0, gt.shape[0], copy_row, 0)
    out_ref[...] = jax.lax.dot_general(
        gt[...].astype(act_dtype),
        vals_ref[0].astype(act_dtype),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def nm_spmm_shared_pallas(
    act: jax.Array,
    vals: jax.Array,
    rows: jax.Array,
    *,
    block_b: int = 128,
    interpret: bool = False,
):
    b, k = act.shape
    nf, kc, tf = vals.shape
    assert rows.shape == (nf, kc)
    block_b = min(block_b, b)
    assert b % block_b == 0
    grid = (b // block_b, nf)
    return pl.pallas_call(
        functools.partial(_spmm_shared_kernel, act_dtype=act.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (1, 1, kc),
                lambda i, j: (j, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (k, block_b),
                lambda i, j: (0, i),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, kc, tf),
                lambda i, j: (j, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_b, tf),
            lambda i, j: (i, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b, nf * tf), jnp.float32),
        scratch_shapes=[pltpu.VMEM((kc, block_b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                pltpu.GridDimensionSemantics.PARALLEL,
                pltpu.GridDimensionSemantics.PARALLEL,
            )
        ),
        interpret=interpret,
        name="nm_spmm_shared",
    )(rows.reshape(nf, 1, kc), act.astype(jnp.float32).T, vals)
