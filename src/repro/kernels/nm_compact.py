"""SORE — N:M sparse online reduction engine, as a Pallas TPU kernel.

The paper's SORE is a 32-lane array of top-K sorters that turns a dense
M-group stream into (top-N values, within-group indices) in M cycles.
The TPU-native analogue is a VMEM-tiled vector kernel: each grid step
loads a (128, W) tile, selects the N largest-|x| per consecutive-M group
with a strictly-earlier-index tie-break (exactly what a greater-than-only
hardware sorter does), and writes the packed (128, W*N/M) values and
uint8 offsets.

Layout: Mosaic cannot split the lane axis into (W/M, M) groups, so the
tile is transposed into a VMEM scratch — groups then run down sublanes —
and read back as M *planes*, plane s holding in-group offset s of every
group (a sublane-strided load; strided VMEM access needs 32-bit data in
a 128-lane memref, which fixes the row block at 128).  Selection is then
elementwise across the planes: N rounds of max (no argsort), then an
N-element sorting network so survivors appear in ascending group offset,
matching the ``ref.py``/`nm_pack` layout and the compact format of
Mishra et al. (the paper's [21]).  Packed planes go back through a
strided store and a transpose.

The helpers below (``tile_planes``, ``select_topn_planes``,
``planes_tile``) are shared with fused_update and grad_compress.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Row block of every lane-grouped kernel: the transposed tile is a
# (W, ROWS) scratch, and Mosaic's strided loads/stores need 128 lanes.
ROWS = 128


def tile_planes(scratch, tile: jax.Array, m: int) -> list:
    """(ROWS, W) f32 tile -> m planes (W/m, ROWS), plane s = offset s."""
    scratch[...] = tile.T
    g = tile.shape[1] // m
    return [scratch[pl.ds(s, g, stride=m), :] for s in range(m)]


def planes_tile(scratch, planes: list) -> jax.Array:
    """Inverse of :func:`tile_planes`: k planes (G, ROWS) -> (ROWS, G*k),
    plane j landing on columns j, j+k, j+2k, ..."""
    k = len(planes)
    g = planes[0].shape[0]
    for j, p in enumerate(planes):
        scratch[pl.ds(j, g, stride=k), :] = p
    return scratch[...].T


def select_topn_planes(planes: list, n: int):
    """m value planes -> (n value planes, n int32 offset planes).

    Per group (one element of every plane): the n largest |x|, ties to
    the earlier offset (each round takes the first offset attaining the
    max), returned in ascending offset order.  Values are selected, not
    summed, so they come back bit for bit (signed zeros included)."""
    m = len(planes)
    score = [jnp.abs(p.astype(jnp.float32)) for p in planes]
    vals, idxs = [], []
    for _ in range(n):
        mx = functools.reduce(jnp.maximum, score)
        j = jnp.full(mx.shape, m, jnp.int32)
        for s in reversed(range(m)):
            j = jnp.where(score[s] == mx, s, j)
        v = planes[0]
        for s in range(1, m):
            v = jnp.where(j == s, planes[s], v)
        vals.append(v)
        idxs.append(j)
        score = [jnp.where(j == s, -jnp.inf, sc) for s, sc in enumerate(score)]
    # sort the n (val, idx) pairs ascending by idx — O(n^2) network, n tiny
    for a in range(n):
        for b in range(a + 1, n):
            swap = idxs[a] > idxs[b]
            ia, ib, va, vb = idxs[a], idxs[b], vals[a], vals[b]
            idxs[a] = jnp.where(swap, ib, ia)
            idxs[b] = jnp.where(swap, ia, ib)
            vals[a] = jnp.where(swap, vb, va)
            vals[b] = jnp.where(swap, va, vb)
    return vals, idxs


def survivor_planes(idxs: list, m: int) -> list:
    """n offset planes -> m bool planes: is offset s kept in its group."""
    out = []
    for s in range(m):
        hit = idxs[0] == s
        for i in idxs[1:]:
            hit = hit | (i == s)
        out.append(hit)
    return out


def idx_tile(scratch, idxs: list, idx_bits: int) -> jax.Array:
    """n int32 offset planes -> the (ROWS, Kc) uint8 index block, or the
    u4 plane (ROWS, ceil(Kc/2)): two offsets per byte, low nibble first
    (``core.sparsity.pack_idx_u4`` layout; an odd Kc pads a zero high
    nibble)."""
    k = len(idxs)
    g = idxs[0].shape[0]
    for j, p in enumerate(idxs):
        scratch[pl.ds(j, g, stride=k), :] = p
    kc = g * k
    if idx_bits == 8:
        return scratch[...].T.astype(jnp.uint8)
    half = (kc + 1) // 2
    if kc % 2:
        scratch[pl.ds(kc, 1), :] = jnp.zeros((1, ROWS), jnp.int32)
    lo = scratch[pl.ds(0, half, stride=2), :]
    hi = scratch[pl.ds(1, half, stride=2), :]
    return (lo | (hi << 4)).T.astype(jnp.uint8)


def idx_scratch(kc: int, idx_bits: int):
    """VMEM scratch for :func:`idx_tile` (u4: room for an odd Kc's pad
    row)."""
    return pltpu.VMEM((kc + kc % 2 if idx_bits == 4 else kc, ROWS),
                      jnp.int32)


def _compact_kernel(x_ref, vals_ref, idx_ref, xt, vt, it, *, n: int, m: int,
                    idx_bits: int):
    planes = tile_planes(xt, x_ref[...].astype(jnp.float32), m)
    v, i = select_topn_planes(planes, n)
    vals_ref[...] = planes_tile(vt, v).astype(vals_ref.dtype)
    idx_ref[...] = idx_tile(it, i, idx_bits)


def nm_compact_pallas(x: jax.Array, n: int, m: int, *, idx_bits: int = 8,
                      interpret: bool = False):
    """Pack (R, W) -> values (R, W*n/m), idx uint8 along the last axis.

    R must be a multiple of :data:`ROWS` (``kernels.ops`` reshapes and
    pads to that).  ``idx_bits=4`` emits the u4 index plane
    (R, ceil(W*n/m/2)) straight from the selection tile."""
    r, w = x.shape
    assert w % m == 0 and r % ROWS == 0, (r, w, m)
    kc = w // m * n
    kci = (kc + 1) // 2 if idx_bits == 4 else kc
    return pl.pallas_call(
        functools.partial(_compact_kernel, n=n, m=m, idx_bits=idx_bits),
        grid=(r // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, w), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=(
            pl.BlockSpec((ROWS, kc), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((ROWS, kci), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((r, kc), x.dtype),
            jax.ShapeDtypeStruct((r, kci), jnp.uint8),
        ),
        scratch_shapes=[pltpu.VMEM((w, ROWS), jnp.float32),
                        pltpu.VMEM((kc, ROWS), jnp.float32),
                        idx_scratch(kc, idx_bits)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,)),
        interpret=interpret,
        name=f"nm_compact_{n}_{m}" + ("_u4" if idx_bits == 4 else ""),
    )(x)
