"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``ref_*`` function defines the exact semantics the corresponding
kernel must match (tests assert allclose across shape/dtype sweeps).
These are also the implementations used on non-TPU backends and inside
the multi-pod dry-run (XLA fuses them well, and they keep the lowered
HLO clean for roofline accounting).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sparsity as S


def ref_nm_compact(x: jax.Array, n: int, m: int):
    """SORE oracle: pack x N:M along the last axis -> (values, indices)."""
    return S.nm_pack(x, n, m, axis=-1)


def ref_nm_spmm(act: jax.Array, vals: jax.Array, idx: jax.Array, n: int, m: int,
                idx_bits: int = 8):
    """Element-mode N:M sparse matmul oracle.

    act:  (B, K) dense activations
    vals: (Kc, F) packed weight values, Kc = K*n/m, pattern along K per column
    idx:  (Kc, F) uint8 within-group offsets — or the u4 plane
          (ceil(Kc/2), F) with ``idx_bits=4``, two offsets per byte
    out:  (B, F) fp32
    """
    from repro.kernels.nm_spmm_shared import decompress_nm

    w = decompress_nm(vals, idx, n, m, axis=0, idx_bits=idx_bits)
    return jnp.dot(act, w.astype(act.dtype), preferred_element_type=jnp.float32)


def ref_nm_spmm_shared(act: jax.Array, vals: jax.Array, rows: jax.Array):
    """Shared-pattern reduced-K matmul oracle.

    act:  (B, K)
    vals: (nf_tiles, Kc, TF) per-output-tile packed weights
    rows: (nf_tiles, Kc) int32 absolute K-row of each packed slot
    out:  (B, nf_tiles*TF) fp32
    """
    def per_tile(v, r):
        a = jnp.take(act, r, axis=1)  # (B, Kc)
        return jnp.dot(a, v.astype(act.dtype), preferred_element_type=jnp.float32)

    outs = jax.vmap(per_tile, in_axes=(0, 0), out_axes=1)(vals, rows)
    return outs.reshape(act.shape[0], -1)


def bf16_round(x: jax.Array) -> jax.Array:
    """f32 -> the nearest bf16 value (ties to even), kept in f32.

    Bitwise equal to ``x.astype(bfloat16).astype(float32)`` on every
    finite x and on +-inf; finite values past bf16's largest round to
    +-inf, as that convert does.  NaN stays NaN, sign kept, quiet bit
    set.  Spelled in integer ops on purpose: a compiler may drop an
    f32 -> bf16 -> f32 convert pair as excess precision (XLA on TPU
    does), and that would erase exactly the rounding error the
    error-feedback residual must carry.  The compress kernels and the
    sync's reference semantics (``optim.compress.compress_leaf``) round
    through this one helper.
    """
    u32 = jnp.uint32
    b = jax.lax.bitcast_convert_type(x, u32)
    rounded = (b + (u32(0x7FFF) + ((b >> u32(16)) & u32(1)))) & u32(0xFFFF0000)
    quiet_nan = (b | u32(0x00400000)) & u32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(jnp.where(x != x, quiet_nan, rounded),
                                        jnp.float32)


def ref_grad_compress(g: jax.Array, err: jax.Array, n: int, m: int):
    """EF compress oracle: (g, err) -> (bf16 vals, uint8 idx, new_err f32).

    t = g + err; top-n |t| per consecutive-m group along the last axis;
    the wire payload is bf16, and the residual subtracts the *rounded*
    values so error feedback telescopes exactly: decoded + new_err ==
    g + err bitwise in f32.
    """
    t = (g.astype(jnp.float32) + err.astype(jnp.float32))
    vals, idx = S.nm_pack(t, n, m, axis=-1)
    dec = S.nm_unpack_n(bf16_round(vals), idx, n, m, axis=-1)
    return vals.astype(jnp.bfloat16), idx, t - dec


def ref_grad_decompress_mean(vals: jax.Array, idx: jax.Array, n: int, m: int):
    """Pod-mean decompress oracle: (P, Kc) payloads -> (K,) dense f32."""
    dec = S.nm_unpack_n(vals.astype(jnp.float32), idx, n, m, axis=-1)
    return dec.mean(axis=0)


def ref_fused_update(
    w: jax.Array,
    g: jax.Array,
    v: jax.Array,
    *,
    lr: float,
    mu: float,
    wd: float,
    lam: float,
    n: int,
    m: int,
):
    """WUVE + SORE pre-generation oracle (momentum SGD, fp32 master).

    Returns (new_w fp32, new_v fp32, wff_vals bf16, wff_idx uint8) where the
    packed pair is the N:M compaction of the *updated* weights along the
    last axis (the FF contraction axis) — the paper's pre-generation
    dataflow: FF never reloads dense weights.
    """
    mask = S.nm_mask(w, n, m, axis=-1)
    g_eff = g + wd * w + lam * jnp.where(mask, 0.0, w)
    new_v = mu * v + g_eff
    new_w = w - lr * new_v
    vals, idx = S.nm_pack(new_w, n, m, axis=-1)
    return new_w, new_v, vals.astype(jnp.bfloat16), idx
