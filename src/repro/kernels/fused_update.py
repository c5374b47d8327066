"""Fused WUVE + SORE pre-generation — Pallas TPU kernel.

The paper's pre-generation dataflow (Fig. 11c): the optimizer's weight
update is fused with the N:M compaction so the FF/BP stages of the next
iteration load only compact sparse weights — saving external-memory
bandwidth and storage whenever sparsity > 50%.

One grid step performs, on a (128, W) fp32 master-weight tile (the
layout and the selection helpers are nm_compact's):

  mask  = N:M survivor mask of w (SR-STE's sparse-refined target)
  g_eff = g + wd*w + lam*(1-mask)*w        # SR-STE regularized gradient
  v'    = mu*v + g_eff                     # momentum (fp32, WUVE lane)
  w'    = w - lr*v'
  (vals, idx) = pack_{N:M}(w')             # SORE, fused — bf16 + uint8

lr/mu/wd/lam stream in as (1,1) fp32 scalars so schedules don't retrace.

Wired into training via ``optim/sgd.update(use_pallas=True)``: the
caller moves the FF contraction axis last, the kernel's in-VMEM decay
mask is bitwise-identical to the stored previous-WU mask (both score the
same fp32 master with the same earlier-index tie-break), and its packed
output becomes the pre-generated FF operand of the next step
(tests/test_pregen.py pins jnp-vs-kernel trajectories bitwise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.nm_compact import (ROWS, idx_tile, idx_scratch,
                                      planes_tile, select_topn_planes,
                                      survivor_planes, tile_planes)


def _fused_update_kernel(
    lr_ref, mu_ref, wd_ref, lam_ref,
    w_ref, g_ref, v_ref,
    w_out, v_out, vals_out, idx_out,
    wt, vt, it,
    *, n: int, m: int,
):
    w = w_ref[...]
    # survivor mask of the *current* weights (pre-update), per SR-STE
    _, keep_idx = select_topn_planes(tile_planes(wt, w, m), n)
    keep = [k.astype(jnp.float32) for k in survivor_planes(keep_idx, m)]
    mask = planes_tile(wt, keep) > 0.5

    lr = lr_ref[0, 0]
    mu = mu_ref[0, 0]
    wd = wd_ref[0, 0]
    lam = lam_ref[0, 0]

    g_eff = g_ref[...] + wd * w + lam * jnp.where(mask, 0.0, w)
    v_new = mu * v_ref[...] + g_eff
    w_new = w - lr * v_new

    v_out[...] = v_new
    w_out[...] = w_new

    # SORE: pack the updated weights along the last axis
    pv, pi = select_topn_planes(tile_planes(wt, w_new, m), n)
    vals_out[...] = planes_tile(vt, pv).astype(vals_out.dtype)
    idx_out[...] = idx_tile(it, pi, 8)


def fused_update_pallas(
    w: jax.Array,
    g: jax.Array,
    v: jax.Array,
    lr: jax.Array,
    mu: jax.Array,
    wd: jax.Array,
    lam: jax.Array,
    n: int,
    m: int,
    *,
    interpret: bool = False,
):
    """(R, W) fp32 master/grad/momentum -> (w', v', bf16 vals, u8 idx).

    R must be a multiple of ``ROWS`` (``kernels.ops`` reshapes and pads
    to that); the packed pair runs along the last axis."""
    r, k = w.shape
    assert r % ROWS == 0 and k % m == 0, (r, k, m)
    kc = k // m * n
    scal = lambda: pl.BlockSpec(  # noqa: E731
        (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM
    )
    blk = lambda bk: pl.BlockSpec(  # noqa: E731
        (ROWS, bk), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    as2d = lambda s: jnp.asarray(s, jnp.float32).reshape(1, 1)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_fused_update_kernel, n=n, m=m),
        grid=(r // ROWS,),
        in_specs=[scal(), scal(), scal(), scal(), blk(k), blk(k), blk(k)],
        out_specs=(blk(k), blk(k), blk(kc), blk(kc)),
        out_shape=(
            jax.ShapeDtypeStruct((r, k), jnp.float32),
            jax.ShapeDtypeStruct((r, k), jnp.float32),
            jax.ShapeDtypeStruct((r, kc), jnp.bfloat16),
            jax.ShapeDtypeStruct((r, kc), jnp.uint8),
        ),
        scratch_shapes=[pltpu.VMEM((k, ROWS), jnp.float32),
                        pltpu.VMEM((kc, ROWS), jnp.float32),
                        idx_scratch(kc, 8)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(pltpu.GridDimensionSemantics.PARALLEL,)
        ),
        interpret=interpret,
        name=f"fused_update_{n}_{m}",
    )(as2d(lr), as2d(mu), as2d(wd), as2d(lam), w, g, v)
