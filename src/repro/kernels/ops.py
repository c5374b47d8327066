"""Public jit'd wrappers around the Pallas kernels.

``use_pallas=True`` (the default here) runs the Pallas kernel: compiled
by Mosaic on TPU, in ``interpret=True`` mode elsewhere, which executes
the kernel body op by op and is what the test suite checks against the
``ref.py`` oracles.  ``use_pallas=False`` runs the jnp oracle (or its
bitwise-equal vectorized form) through XLA.

Model code reaches ``nm_spmm`` through ``core.operand.nm_apply``, whose
``backend="auto"`` resolves to the kernel on TPU and to jnp elsewhere;
the optimizer and the gradient sync take ``use_pallas=`` from their
builders.  A shape no tiling suits gets whole-dimension blocks (always
a legal Mosaic block), and one no block fits in VMEM raises — no
dispatcher here swaps in the oracle behind the caller's back.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import sparsity as S
from repro.kernels import ref
from repro.kernels.fused_update import fused_update_pallas
from repro.kernels.grad_compress import (
    grad_compress_pallas,
    grad_decompress_mean_pallas,
)
from repro.kernels.nm_compact import ROWS, nm_compact_pallas
from repro.kernels.nm_spmm import nm_spmm_pallas
from repro.kernels.nm_spmm_shared import nm_spmm_shared_pallas

# VMEM a kernel's blocks may take (bytes): under the 16 MiB Mosaic
# scoped-VMEM default, with room for double buffering and scratch.
_VMEM_BUDGET = 12 * 1024 * 1024


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _lane_width(shape, n: int, m: int, idx_bits: int = 8) -> int:
    """Row width W that a lane-grouped kernel tiles a (..., K) array into.

    M-groups run along the last axis and never cross a multiple of M in
    the flattened array, so any W that M divides and that divides the
    array's size re-rows it for free.  W is chosen so the packed
    output block is lane-dense (a multiple of 128 lanes — the u4 plane
    included, which also needs an even compact row so nibble pairs never
    straddle two original rows); without one, W is K itself."""
    k = shape[-1]
    size = math.prod(shape)
    for w in (1024, 512, 2048, 256, 4096, 128):
        lanes = w // m * n // (2 if idx_bits == 4 else 1)
        if (w % m == 0 and size % w == 0 and lanes % 128 == 0
                and (idx_bits == 8 or (k // m * n) % 2 == 0)):
            return w
    return k


def _as_rows(x: jax.Array, w: int) -> jax.Array:
    """(..., K) -> (R, W), R zero-padded up to a multiple of ``ROWS``."""
    x2 = x.reshape(-1, w)
    pad = -x2.shape[0] % ROWS
    return jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2


def _from_rows(y: jax.Array, shape) -> jax.Array:
    return y.reshape(-1)[:math.prod(shape)].reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "use_pallas", "idx_bits"))
def nm_compact(x: jax.Array, n: int, m: int, use_pallas: bool = True,
               idx_bits: int = 8):
    """SORE: pack along the last axis -> (values, uint8 indices).

    ``idx_bits=4`` returns the u4 index plane (two offsets per byte,
    compact axis length ceil(Kc/2)); the Pallas path emits it straight
    from the selection tile, the oracle path packs the oracle's bytes.
    """
    if idx_bits not in (4, 8):
        raise ValueError(f"idx_bits must be 4 or 8, got {idx_bits}")
    shape = x.shape
    kc = shape[-1] // m * n
    kci = (kc + 1) // 2 if idx_bits == 4 else kc
    if not use_pallas:
        v, i = ref.ref_nm_compact(x, n, m)
        if idx_bits == 4:
            i = S.pack_idx_u4(i, axis=-1)
        return v, i
    w = _lane_width(shape, n, m, idx_bits)
    v, i = nm_compact_pallas(_as_rows(x, w), n, m, idx_bits=idx_bits,
                             interpret=_interpret())
    return (_from_rows(v, (*shape[:-1], kc)),
            _from_rows(i, (*shape[:-1], kci)))


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "use_pallas", "idx_bits"))
def nm_spmm(act, vals, idx, n: int, m: int, use_pallas: bool = True,
            idx_bits: int = 8):
    """Element-mode sparse matmul: (B,K) @ packed(Kc,F) -> (B,F) fp32.

    ``idx_bits=4`` consumes the u4 index plane (ceil(Kc/2), F) — two
    in-group offsets per byte, low nibble first (see
    ``core.sparsity.pack_idx_u4``).  The Pallas path fuses the nibble
    expansion into the tile decompress (half the index HBM traffic, no
    dense weight outside VMEM).  Both widths are bitwise identical to
    each other and to the oracle on the same offsets.
    """
    if not use_pallas:
        return ref.ref_nm_spmm(act, vals, idx, n, m, idx_bits=idx_bits)
    b, k = act.shape
    kc, f = vals.shape
    # index rows per K tile: u8 tiles hold 32 rows, and a u4 byte must
    # not straddle two tiles
    quantum = 64 if idx_bits == 4 else 32
    bk = _pick_block(k, (512, 1024, 256, 2048),
                     ok=lambda c: c % m == 0 and (c // m * n) % quantum == 0)
    bf = _pick_block(f, (128,))
    bb = _pick_block(b, (256, 128, 64, 32, 16, 8),
                     ok=lambda c: c * bk * act.dtype.itemsize <= _VMEM_BUDGET // 4)
    return nm_spmm_pallas(
        act, vals, idx, n, m, block_b=bb, block_f=bf, block_k=bk,
        idx_bits=idx_bits, interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def nm_spmm_shared(act, vals, rows, use_pallas: bool = True):
    """Shared-pattern reduced-K matmul: true N/M FLOP saving on the MXU."""
    if not use_pallas:
        return ref.ref_nm_spmm_shared(act, vals, rows)
    b, k = act.shape
    panel = k * 4  # the kernel holds the (K, TB) panel in f32
    bb = _pick_block(b, (128, 256), ok=lambda c: c * panel <= _VMEM_BUDGET)
    if bb * panel > _VMEM_BUDGET:
        raise ValueError(
            f"nm_spmm_shared: a ({k}, {bb}) activation panel exceeds the "
            f"{_VMEM_BUDGET}-byte VMEM budget")
    return nm_spmm_shared_pallas(act, vals, rows, block_b=bb,
                                 interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n", "m", "use_pallas"))
def fused_update(w, g, v, lr, mu, wd, lam, n: int, m: int, use_pallas: bool = True):
    """Momentum-SGD + SR-STE decay + N:M pre-generation, fused."""
    if not use_pallas:
        return ref.ref_fused_update(w, g, v, lr=lr, mu=mu, wd=wd, lam=lam, n=n, m=m)
    shape = w.shape
    kc = shape[-1] // m * n
    rw = _lane_width(shape, n, m)
    nw, nv, vals, idx = fused_update_pallas(
        _as_rows(w, rw), _as_rows(g.astype(jnp.float32), rw),
        _as_rows(v, rw), lr, mu, wd, lam, n, m, interpret=_interpret(),
    )
    packed = (*shape[:-1], kc)
    return (_from_rows(nw, shape), _from_rows(nv, shape),
            _from_rows(vals, packed), _from_rows(idx, packed))


def _jnp_grad_compress(g, err, n: int, m: int):
    """Vectorized jnp EF compress, bitwise-identical to ``ref_grad_compress``.

    The oracle spells the semantics with ``nm_pack``/``nm_unpack_n``
    (top_k + sort + scatter) — readable, but those lower to per-group
    variadic sorts and scatters that dominate the sync step on XLA CPU.
    This path gets the same bits from branchless elementwise ops only:

      * selection: n rounds of masked argmax.  ``jnp.argmax`` keeps the
        *first* occurrence on ties, which is exactly ``lax.top_k``'s
        stable lower-index-wins rule, so the survivor sets and packed
        order (ascending offset after the n-element sort) agree with the
        oracle on every tie pattern.
      * ordering: the n selected offsets are distinct, so an exchange
        (bubble) network of ``minimum``/``maximum`` pairs yields the same
        ascending order as ``jnp.sort`` — without the variadic per-group
        sort XLA CPU would otherwise emit (~10x slower at slab sizes).
      * residual: no decode/scatter at all.  The decoded payload equals
        ``bf16(t)`` at survivor lanes and 0 elsewhere, so
        ``t - decode(payload)`` is just ``where(survivor, t - bf16(t), t)``
        — elementwise, and bitwise the same f32 subtraction the oracle
        performs.

    tests/test_grad_compress.py pins the bitwise equality property.
    """
    t = g.astype(jnp.float32) + err.astype(jnp.float32)
    k = t.shape[-1]
    gg = t.reshape(*t.shape[:-1], k // m, m)
    score = jnp.abs(gg)
    offs = jnp.arange(m, dtype=jnp.int32)
    masked = score
    sel = []
    for _ in range(n):
        i = jnp.argmax(masked, axis=-1).astype(jnp.int32)
        sel.append(i)
        masked = jnp.where(offs == i[..., None], -jnp.inf, masked)
    for a in range(n - 1):
        for b in range(n - 1 - a):
            lo = jnp.minimum(sel[b], sel[b + 1])
            hi = jnp.maximum(sel[b], sel[b + 1])
            sel[b], sel[b + 1] = lo, hi
    idx = jnp.stack(sel, axis=-1)
    vals = jnp.take_along_axis(gg, idx, axis=-1)
    survivor = jnp.zeros(gg.shape, bool)
    for i in sel:
        survivor = survivor | (offs == i[..., None])
    rounded = ref.bf16_round(gg)
    new_err = jnp.where(survivor, gg - rounded, gg).reshape(t.shape)
    kc = k // m * n
    return (vals.astype(jnp.bfloat16).reshape(*t.shape[:-1], kc),
            idx.reshape(*t.shape[:-1], kc).astype(jnp.uint8),
            new_err)


def _jnp_grad_decompress_mean(vals, idx, n: int, m: int):
    """Vectorized pod-mean decompress, bitwise == ``ref_grad_decompress_mean``.

    One-hot multiply-accumulate instead of the oracle's scatter: XLA CPU
    lowers ``put_along_axis`` to a serial per-group scatter loop, while
    the (P, G, n, m) one-hot contraction stays a fused elementwise kernel
    (~5x faster at sync-slab sizes).
    """
    p, kc = vals.shape
    gv = vals.astype(jnp.float32).reshape(p, kc // n, n)
    gi = idx.reshape(p, kc // n, n).astype(jnp.int32)
    offs = jnp.arange(m, dtype=jnp.int32)
    dense = jnp.sum(gv[..., None] * (gi[..., None] == offs), axis=-2)
    return dense.reshape(p, kc // n * m).mean(axis=0)


@functools.partial(jax.jit, static_argnames=("n", "m", "use_pallas"))
def grad_compress(g, err, n: int, m: int, use_pallas: bool = True):
    """Fused EF compress: (g+err) -> (bf16 vals, uint8 idx, new residual).

    Accepts any shape whose last axis is divisible by m (the sync path
    passes (n_pods, bucket) slabs).  Telescoping is exact: the decoded
    payload plus the returned residual equals g + err bitwise in f32.
    """
    if not use_pallas:
        return _jnp_grad_compress(g, err, n, m)
    shape = g.shape
    kc = shape[-1] // m * n
    rw = _lane_width(shape, n, m)
    vals, idx, new_err = grad_compress_pallas(
        _as_rows(g.astype(jnp.float32), rw),
        _as_rows(err.astype(jnp.float32), rw), n, m, interpret=_interpret()
    )
    packed = (*shape[:-1], kc)
    return (_from_rows(vals, packed), _from_rows(idx, packed),
            _from_rows(new_err, shape))


@functools.partial(jax.jit, static_argnames=("n", "m", "use_pallas"))
def grad_decompress_mean(vals, idx, n: int, m: int, use_pallas: bool = True):
    """All-gathered payloads (P, Kc) -> pod-mean dense gradient (K,) f32."""
    if not use_pallas:
        return _jnp_grad_decompress_mean(vals, idx, n, m)
    p, kc = vals.shape
    # packed rows of Wc = W*n/m lanes expand to lane-dense W-wide rows
    k = kc // n * m
    wc = _lane_width((k,), n, m) // m * n
    per_pod = jax.vmap(lambda a: _as_rows(a, wc))
    out = grad_decompress_mean_pallas(per_pod(vals), per_pod(idx), n, m,
                                      interpret=_interpret())
    return _from_rows(out, (k,))


def pack_shared(w: jax.Array, n: int, m: int, tile: int = 128):
    """Host-side packer for the shared mode: (K,F) -> (nf, Kc, TF), rows.

    Pattern is chosen per F-tile by summed |w| over the tile (the same
    scoring the shared-granularity mask in core/sparsity uses), so the
    kernel and ``sparsify(granularity='shared')`` agree exactly.
    """
    k, f = w.shape
    assert f % tile == 0 and k % m == 0
    nf = f // tile
    wt = w.reshape(k, nf, tile)
    score = jnp.abs(wt).astype(jnp.float32).sum(-1)  # (K, nf)
    gsc = score.reshape(k // m, m, nf)
    mask = S.nm_mask(gsc.transpose(2, 0, 1).reshape(nf, -1), n, m, axis=-1)
    mask = mask.reshape(nf, k // m, m)
    # rows: absolute K index of each survivor, ascending
    _, gidx = jax.lax.top_k(
        jnp.where(mask, 1.0, 0.0)
        - jnp.arange(m, dtype=jnp.float32)[None, None, :] * 1e-3,
        n,
    )
    gidx = jnp.sort(gidx, axis=-1)  # (nf, K/m, n)
    base = (jnp.arange(k // m) * m)[None, :, None]
    rows = (gidx + base).reshape(nf, -1).astype(jnp.int32)  # (nf, Kc)
    vals = jax.vmap(lambda r, wti: jnp.take(wti, r, axis=0), in_axes=(0, 1))(
        rows, wt
    )  # (nf, Kc, tile)
    return vals, rows


def packed_bytes(k: int, f: int, n: int, m: int, dtype_bytes: int = 2,
                 idx_bits: int = 8) -> int:
    """HBM footprint of an element-mode packed (K,F) weight."""
    kc = k // m * n
    return kc * f * dtype_bytes + kc * f * idx_bits // 8


def _pick_block(dim: int, candidates, ok=lambda c: True) -> int:
    """First candidate that divides ``dim`` and passes ``ok``; else the
    whole dimension (a block equal to the array dim is always a legal
    Mosaic block, whatever the (8, 128) tiling)."""
    for c in candidates:
        if c <= dim and dim % c == 0 and ok(c):
            return c
    return dim
