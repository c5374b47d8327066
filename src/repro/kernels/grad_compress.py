"""Fused error-feedback gradient compression as Pallas TPU kernels.

The cross-pod sync path ships gradients as N:M packed ``(bf16 vals,
uint8 idx)`` payloads.  Done naively that costs three dense HBM round
trips per bucket (add residual, pack, recompute residual); these two
kernels fuse each side into a single VMEM-resident pass so compression
stays off the critical path (the paper's pre-generation argument,
Fig. 11c, applied to gradients per arXiv 2203.10991):

``grad_compress_pallas``
    (g, err) -> (vals bf16, idx uint8, new_err f32) per tile:
    t = g + err; select top-n |t| per consecutive-m group (same
    greater-than-only tie-break as SORE / ``nm_compact``); the wire
    payload is t rounded to bf16, and the *rounded* value is what the
    new residual subtracts — so error feedback telescopes exactly in
    f32 arithmetic: decoded + new_err == g + err bitwise.

``grad_decompress_mean_pallas``
    All-gathered payloads (P, R, Wc) -> dense mean (R, W) without ever
    materializing the P dense gradients: each grid step expands its
    packed tile with m-way selects and reduces over the pod axis in
    VMEM.

Both use nm_compact's layout: a (128, W) tile is transposed into VMEM
and handled as per-offset planes, so groups never split the lane axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nm_compact import (ROWS, idx_scratch, idx_tile,
                                      planes_tile, select_topn_planes,
                                      survivor_planes, tile_planes)
from repro.kernels.ref import bf16_round


def _compress_kernel(g_ref, e_ref, vals_ref, idx_ref, err_ref, tt, vt, it,
                     *, n: int, m: int):
    t = g_ref[...].astype(jnp.float32) + e_ref[...].astype(jnp.float32)
    planes = tile_planes(tt, t, m)
    v, i = select_topn_planes(planes, n)
    # the residual must see the *wire* (bf16-rounded) values, so the
    # rounding error is carried forward rather than silently dropped
    err = [jnp.where(kept, p - bf16_round(p), p)
           for kept, p in zip(survivor_planes(i, m), planes)]
    vals_ref[...] = planes_tile(vt, v).astype(jnp.bfloat16)
    idx_ref[...] = idx_tile(it, i, 8)
    err_ref[...] = planes_tile(tt, err)


def grad_compress_pallas(g: jax.Array, e: jax.Array, n: int, m: int, *,
                         interpret: bool = False):
    """(R, W) grads + residual -> bf16 vals, uint8 idx (R, W*n/m), err
    (R, W).  R must be a multiple of ``ROWS``."""
    r, k = g.shape
    assert k % m == 0 and r % ROWS == 0, (r, k, m)
    kc = k // m * n
    blk = lambda bk: pl.BlockSpec(  # noqa: E731
        (ROWS, bk), lambda i: (i, 0), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_compress_kernel, n=n, m=m),
        grid=(r // ROWS,),
        in_specs=[blk(k), blk(k)],
        out_specs=(blk(kc), blk(kc), blk(k)),
        out_shape=(
            jax.ShapeDtypeStruct((r, kc), jnp.bfloat16),
            jax.ShapeDtypeStruct((r, kc), jnp.uint8),
            jax.ShapeDtypeStruct((r, k), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((k, ROWS), jnp.float32),
                        pltpu.VMEM((kc, ROWS), jnp.float32),
                        idx_scratch(kc, 8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,)
        ),
        interpret=interpret,
        name=f"grad_compress_{n}_{m}",
    )(g, e)


def _decompress_mean_kernel(vals_ref, idx_ref, out_ref, vt, it, dt,
                            *, n: int, m: int):
    p_count = vals_ref.shape[0]
    g = vals_ref.shape[2] // n
    acc = [jnp.zeros((g, ROWS), jnp.float32) for _ in range(m)]
    for p in range(p_count):
        vt[...] = vals_ref[p].astype(jnp.float32).T
        it[...] = idx_ref[p].astype(jnp.int32).T
        v = [vt[pl.ds(j, g, stride=n), :] for j in range(n)]
        i = [it[pl.ds(j, g, stride=n), :] for j in range(n)]
        for s in range(m):
            dec = jnp.where(i[0] == s, v[0], 0.0)
            for vj, ij in zip(v[1:], i[1:]):
                dec = dec + jnp.where(ij == s, vj, 0.0)
            acc[s] = acc[s] + dec
    out_ref[...] = planes_tile(dt, [a / p_count for a in acc])


def grad_decompress_mean_pallas(vals: jax.Array, idx: jax.Array, n: int,
                                m: int, *, interpret: bool = False):
    """All-gathered packed payloads (P, R, Wc) -> pod-mean dense (R, W)
    f32, W = Wc*m/n.  R must be a multiple of ``ROWS``."""
    p, r, wc = vals.shape
    assert wc % n == 0 and r % ROWS == 0, (p, r, wc, n)
    w = wc // n * m
    blk = pl.BlockSpec((p, ROWS, wc), lambda i: (0, i, 0),
                       memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_decompress_mean_kernel, n=n, m=m),
        grid=(r // ROWS,),
        in_specs=[blk, blk],
        out_specs=pl.BlockSpec((ROWS, w), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((wc, ROWS), jnp.float32),
                        pltpu.VMEM((wc, ROWS), jnp.int32),
                        pltpu.VMEM((w, ROWS), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,)
        ),
        interpret=interpret,
        name=f"grad_decompress_mean_{n}_{m}",
    )(vals, idx)
