"""Attention: GQA + RoPE + qk-norm + sliding-window + MLA, train & decode.

Memory-sane by construction: no S x S tensor reaches HBM.  Training and
prefill attention goes through ``dot_product_attention``, which picks
from what it can observe: on TPU a plain causal self-attention runs the
fused block-sparse Pallas splash kernel (blocks above the diagonal
skipped, the backward recomputing scores from the logsumexp); every
other call (CPU, MLA's unequal head dims, odd lengths, sequence
parallelism) runs ``chunked_attention``, an online-softmax scan over KV
chunks in pure JAX, as does encoder and cross attention (non-causal).  Sliding-window attention is *banded*
— a scan over query chunks that dynamic-slices only the in-window KV
span — so SWA costs O(S*W) FLOPs in the compiled HLO, not O(S^2) (this
is what makes gemma3/hymba long_500k honest); it and decode stay pure
JAX on every backend.

All projections route through BDWP (core/bdwp) so N:M sparse training
applies to attention weights exactly as the paper does for ViT.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash,
    splash_attention_mask as splash_mask,
)
from jax.sharding import PartitionSpec as P

from repro.core.sparsity import SparsityConfig
from repro.models import layers as L
from repro.sharding import rules as R
from repro.sharding.rules import BATCH, act

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 10000.0
    qk_norm: bool = False
    qkv_bias: bool = False
    window: Optional[int] = None          # sliding-window width (gemma3 local)
    # MLA (deepseek-v2): when kv_lora is set, the layer uses compressed KV.
    kv_lora: Optional[int] = None
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: Optional[int] = None
    chunk_q: int = 1024
    chunk_kv: int = 1024


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def attn_init(key, cfg: AttnConfig):
    ks = jax.random.split(key, 8)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p, s = {}, {}
    if cfg.kv_lora is None:
        for i, (name, dout) in enumerate(
            [("q_proj", h * hd), ("k_proj", kv * hd), ("v_proj", kv * hd)]
        ):
            pp, ss = L.dense_init(ks[i], d, dout, axes=("embed", "heads" if name == "q_proj" else "kv"),
                                  bias=cfg.qkv_bias)
            p[name], s[name] = pp, ss
        pp, ss = L.dense_init(ks[3], h * hd, d, axes=("heads", "embed"))
        p["o_proj"], s["o_proj"] = pp, ss
        if cfg.qk_norm:
            p["q_norm"] = {"norm_scale": jnp.ones((hd,), jnp.float32)}
            p["k_norm"] = {"norm_scale": jnp.ones((hd,), jnp.float32)}
            s["q_norm"] = {"norm_scale": (None,)}
            s["k_norm"] = {"norm_scale": (None,)}
    else:
        dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
        dv = cfg.v_head_dim or dn
        pp, ss = L.dense_init(ks[0], d, h * (dn + dr), axes=("embed", "heads"))
        p["q_proj"], s["q_proj"] = pp, ss
        pp, ss = L.dense_init(ks[1], d, cfg.kv_lora + dr, axes=("embed", None))
        p["kv_down"], s["kv_down"] = pp, ss
        pp, ss = L.dense_init(ks[2], cfg.kv_lora, h * dn, axes=(None, "heads"))
        p["k_up"], s["k_up"] = pp, ss
        pp, ss = L.dense_init(ks[3], cfg.kv_lora, h * dv, axes=(None, "heads"))
        p["v_up"], s["v_up"] = pp, ss
        pp, ss = L.dense_init(ks[4], h * dv, d, axes=("heads", "embed"))
        p["o_proj"], s["o_proj"] = pp, ss
        p["ckv_norm"], sn = L.rmsnorm_init(cfg.kv_lora)
        s["ckv_norm"] = {"norm_scale": (None,)}
    return p, s


# ---------------------------------------------------------------------------
# Core chunked attention (online softmax over KV blocks)
# ---------------------------------------------------------------------------


def _largest_divisor(n: int, cap: int) -> int:
    """Largest divisor of n that is <= cap (static chunk sizing)."""
    cap = min(cap, n)
    for c in range(cap, 0, -1):
        if n % c == 0:
            return c
    return 1


def _gqa_logits(q, k):
    """q: (B,Sq,Hkv,G,D), k: (B,Ck,Hkv,D) -> (B,Hkv,G,Sq,Ck)"""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)


def chunked_attention(q, k, v, *, causal: bool, q_offset, chunk_kv: int = 1024,
                      kv_len_mask: Optional[int] = None):
    """Online-softmax attention, scanning KV chunks.

    q: (B, Sq, H, D); k, v: (B, Skv, Hkv, D); q_offset: scalar — absolute
    position of q[0] (for causal masking of prefill continuations).
    """
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    chunk_kv = _largest_divisor(skv, chunk_kv)
    nk = skv // chunk_kv
    qg = q.reshape(b, sq, hkv, g, d)
    scale = d ** -0.5
    kc = k.reshape(b, nk, chunk_kv, hkv, d)
    vc = v.reshape(b, nk, chunk_kv, hkv, dv)
    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        logits = _gqa_logits(qg, kj) * scale  # (B,Hkv,G,Sq,Ck)
        k_pos = j * chunk_kv + jnp.arange(chunk_kv)
        mask = jnp.ones((sq, chunk_kv), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if kv_len_mask is not None:
            mask &= k_pos[None, :] < kv_len_mask
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (jnp.arange(nk), kc.swapaxes(0, 1), vc.swapaxes(0, 1))
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Fused causal attention (Pallas splash kernel on TPU) and the dispatch
# ---------------------------------------------------------------------------


def _fused_block(s: int) -> Optional[int]:
    """Block of the fused kernel along both sequence axes: the largest
    of 512, 256, 128 that divides ``s`` (None: no such block)."""
    return next((blk for blk in (512, 256, 128) if s % blk == 0), None)


def _kernel_layout(b: int, h: int, hkv: int):
    """How the fused kernel lies over the active activation mesh.

    Returns ``(mesh, spec)``: the ``(B, H, S, D)`` PartitionSpec of a
    ``shard_map`` with the batch over the dp axes and heads over
    ``"model"``, or ``(None, None)`` on one device: an active mesh of
    one, or no active mesh in a process that has one device.  Returns
    None where the kernel cannot be laid out: sequence parallelism, no
    active mesh among several devices (XLA cannot partition a Mosaic
    kernel), a batch or head count the axes do not divide, or another
    mesh axis of more than one device (a vmapped pod axis).
    """
    mesh, dp, sp = R.act_context()
    if sp:
        return None
    if mesh is None:
        return (None, None) if jax.device_count() == 1 else None
    if mesh.size == 1:
        return None, None
    dp = tuple(a for a in (dp or ()) if a in mesh.axis_names)
    n_dp = 1
    for a in dp:
        n_dp *= mesh.shape[a]
    n_tp = mesh.shape.get("model", 1)
    others = [a for a in mesh.axis_names
              if a not in dp and a != "model" and mesh.shape[a] > 1]
    if others or b % n_dp or h % n_tp or hkv % n_tp:
        return None
    return mesh, P(dp or None, "model" if n_tp > 1 else None, None, None)


def fused_path_ok(q, k, v, *, causal: bool, q_offset, kv_len_mask) -> bool:
    """Whether ``dot_product_attention`` runs the fused kernel.

    All of: a TPU (the active mesh's, else the default backend); causal
    with a static ``q_offset`` of 0 and no ``kv_len_mask``; as many
    queries as keys, a multiple of 128; one head dim for q, k and v, a
    multiple of 64 and at most 256; query heads a multiple of KV heads;
    and a layout over the active mesh (``_kernel_layout``).
    """
    mesh = R.act_context()[0]
    platform = (mesh.devices.flat[0].platform if mesh is not None
                else jax.default_backend())
    b, s, h, d = q.shape
    return (platform == "tpu" and causal
            and isinstance(q_offset, int) and q_offset == 0
            and kv_len_mask is None
            and k.shape[1] == s and _fused_block(s) is not None
            and k.shape[-1] == v.shape[-1] == d and d % 64 == 0 and d <= 256
            and h % k.shape[2] == 0
            and _kernel_layout(b, h, k.shape[2]) is not None)


def _splash_kernel(h: int, s: int, interpret: bool):
    """The splash kernel for ``h`` query heads of ``s`` causal positions:
    one block size (``_fused_block``) in every phase, and one backward
    kernel for dq, dk and dv.  On TPU v5e at S 4096 that beat separate
    dq and dkv kernels and blocks of 256 or 1024 (PERF.md §5)."""
    blk = _fused_block(s)
    sizes = splash.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        use_fused_bwd_kernel=True)
    mask = splash_mask.MultiHeadMask([splash_mask.CausalMask((s, s))] * h)
    return splash.make_splash_mha(mask, block_sizes=sizes, head_shards=1,
                                  q_seq_shards=1, interpret=interpret)


def fused_causal_attention(q, k, v, *, interpret: bool = False):
    """Causal attention through the splash kernel: q (B, S, H, D),
    k and v (B, S, Hkv, D), H a multiple of Hkv; ``interpret`` runs it
    in Pallas interpret mode (tests on the CPU).

    The kernel takes bf16 (the operand dtype) in, accumulates in fp32,
    keeps fp32 softmax statistics and has no scale argument: the
    ``D**-0.5`` scale goes onto q in fp32 before its one cast.
    """
    b, s, h, d = q.shape
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(k.dtype)
    mesh, spec = _kernel_layout(b, h, k.shape[2])

    def run(q_, k_, v_):  # (B, H, S, D), the local shard under a mesh
        kernel = _splash_kernel(q_.shape[1], s, interpret)
        return jax.vmap(kernel)(q_, k_, v_)

    if mesh is not None:
        run = jax.shard_map(run, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec, check_vma=False)
    out = run(*(x.swapaxes(1, 2) for x in (qs, k, v)))
    return out.swapaxes(1, 2).astype(q.dtype)


# Counters of the attention sites traced, by path; see ``count_paths``.
_PATH_COUNTS: list = []


@contextlib.contextmanager
def count_paths(step: str):
    """Count the ``dot_product_attention`` sites traced inside, by the
    path each took, and report them on stderr when the trace is done.
    A scanned layer stack is one site: its body is traced once."""
    counts = collections.Counter()
    _PATH_COUNTS.append(counts)
    try:
        yield counts
    finally:
        _PATH_COUNTS.remove(counts)
    if counts:
        print(f"{step}: attention sites traced: fused {counts['fused']}, "
              f"chunked {counts['chunked']}", file=sys.stderr, flush=True)


def dot_product_attention(q, k, v, *, causal: bool, q_offset=0,
                          chunk_kv: int = 1024,
                          kv_len_mask: Optional[int] = None):
    """Training and prefill attention: the fused kernel where
    ``fused_path_ok``, else ``chunked_attention``."""
    fused = fused_path_ok(q, k, v, causal=causal, q_offset=q_offset,
                          kv_len_mask=kv_len_mask)
    for counts in _PATH_COUNTS:
        counts["fused" if fused else "chunked"] += 1
    if fused:
        return fused_causal_attention(q, k, v)
    return chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                             chunk_kv=chunk_kv, kv_len_mask=kv_len_mask)


def banded_attention(q, k, v, *, window: int, chunk_q: int = 1024):
    """Sliding-window causal attention with true O(S*W) FLOPs.

    Scans query chunks; each step dynamic-slices the static-size KV band
    [chunk_start - W_pad, chunk_start + Cq) and masks to the exact window.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    chunk_q = _largest_divisor(s, chunk_q)
    nq = s // chunk_q
    w_pad = ((window + chunk_q - 1) // chunk_q) * chunk_q  # static band padding
    span = w_pad + chunk_q
    scale = d ** -0.5
    # pad kv at the front so every band slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (w_pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (w_pad, 0), (0, 0), (0, 0)))

    def step(_, i):
        q0 = i * chunk_q
        qi = jax.lax.dynamic_slice_in_dim(q, q0, chunk_q, axis=1)
        ki = jax.lax.dynamic_slice_in_dim(kp, q0, span, axis=1)  # [q0-wpad, q0+Cq)
        vi = jax.lax.dynamic_slice_in_dim(vp, q0, span, axis=1)
        qg = qi.reshape(b, chunk_q, hkv, g, d)
        logits = _gqa_logits(qg, ki) * scale  # (B,Hkv,G,Cq,span)
        q_pos = q0 + jnp.arange(chunk_q)
        k_pos = q0 - w_pad + jnp.arange(span)  # absolute (pre-pad coords)
        mask = (q_pos[:, None] >= k_pos[None, :]) \
            & (q_pos[:, None] - k_pos[None, :] < window) \
            & (k_pos[None, :] >= 0)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd",
            jax.nn.softmax(logits, axis=-1).astype(vi.dtype), vi,
            preferred_element_type=jnp.float32,
        )
        return None, out.reshape(b, chunk_q, h, d)

    _, outs = jax.lax.scan(step, None, jnp.arange(nq))
    out = outs.swapaxes(0, 1).reshape(b, s, h, d)
    return out.astype(q.dtype)


def decode_attention(q1, k_cache, v_cache, cur_pos, *, window: Optional[int] = None):
    """Single-step decode: q1 (B,1,H,D) vs cache (B,Smax,Hkv,D).

    ``cur_pos`` is either a scalar (whole batch at one position — the
    classic synchronized-decode path) or a (B,) vector of per-request
    positions (continuous batching: every slot is at its own depth).

    For SWA layers with a scalar position only the last `window`
    positions are sliced (static size), so FLOPs/bytes are O(W) not
    O(Smax); with per-slot positions the slice start would differ per
    row, so the window is enforced by masking instead.  For global
    layers the full cache participates; under a sequence-sharded cache
    GSPMD turns the softmax/PV reductions into the distributed
    flash-decoding pattern (partial max/sum + all-reduce).
    """
    b, _, h, d = q1.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = d ** -0.5
    cur_pos = jnp.asarray(cur_pos)
    per_slot = cur_pos.ndim > 0
    cur_b = cur_pos if per_slot else jnp.broadcast_to(cur_pos, (b,))  # (B,)
    if window is not None and window < smax and not per_slot:
        start = jnp.clip(cur_pos + 1 - window, 0, smax - window)
        kc = jax.lax.dynamic_slice_in_dim(k_cache, start, window, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v_cache, start, window, axis=1)
        k_pos = start + jnp.arange(window)
    else:
        kc, vc = k_cache, v_cache
        k_pos = jnp.arange(smax)
    qg = q1.reshape(b, 1, hkv, g, d)
    logits = _gqa_logits(qg, kc) * scale  # (B,Hkv,G,1,S)
    mask = k_pos[None, :] <= cur_b[:, None]  # (B,S)
    if window is not None and per_slot:
        mask &= (cur_b[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[:, None, None, None, :], logits, NEG_INF)
    attn = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", attn.astype(vc.dtype), vc,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q1.dtype)


# ---------------------------------------------------------------------------
# Full attention layer (projections + cache plumbing)
# ---------------------------------------------------------------------------


def _split_heads(x, n, d):
    return x.reshape(*x.shape[:-1], n, d)


def attn_apply(p, x, cfg: AttnConfig, sp_cfg: SparsityConfig, *,
               positions, cache=None, layer_window: Optional[int] = None,
               decode: bool = False, per_slot: bool = False):
    """Returns (out, new_cache).  cache: dict(k, v) or dict(ckv, kpe) for MLA.

    per_slot=True (decode only): cache reads/writes are indexed by the
    per-row `positions` instead of the shared `cache["pos"]` cursor, so
    each batch row is an independent request slot (continuous batching).
    """
    if cfg.kv_lora is not None:
        return _mla_apply(p, x, cfg, sp_cfg, positions=positions, cache=cache,
                          decode=decode, per_slot=per_slot)
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    q = _split_heads(L.dense_apply(p["q_proj"], x, "attn/q_proj", sp_cfg), h, hd)
    k = _split_heads(L.dense_apply(p["k_proj"], x, "attn/k_proj", sp_cfg), kv, hd)
    v = _split_heads(L.dense_apply(p["v_proj"], x, "attn/v_proj", sp_cfg), kv, hd)
    if cfg.qk_norm:
        q = L.rmsnorm_apply(p["q_norm"], q)
        k = L.rmsnorm_apply(p["k_norm"], k)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    # TP anchor AFTER rope: rope's position broadcast is unsharded, and
    # anchoring before it lets GSPMD replicate the batch through the
    # rope elementwise chain (observed: full-batch fp32 q/k all-gathers)
    q = act(q, BATCH, None, "model", None)
    k = act(k, BATCH, None, "model", None)
    v = act(v, BATCH, None, "model", None)
    window = layer_window

    if decode:
        assert cache is not None
        if per_slot:
            # slot-indexed cache write: every request (batch row) sits at
            # its own position — `positions` (B,1) is the absolute
            # position the incoming token is written to (continuous
            # batching: rows join/leave the batch independently)
            b = x.shape[0]
            wpos = jnp.clip(positions[:, -1].astype(jnp.int32), 0,
                            cache["k"].shape[1] - 1)
            b_idx = jnp.arange(b)
            k_cache = cache["k"].at[b_idx, wpos].set(
                k[:, 0].astype(cache["k"].dtype))
            v_cache = cache["v"].at[b_idx, wpos].set(
                v[:, 0].astype(cache["v"].dtype))
            cur = positions[:, -1]
        else:
            cur = cache["pos"]
            k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cur, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cur, axis=1)
        # anchor: batch-sharded cache, heads over TP only when divisible —
        # without this GSPMD reshards heads over a subgroup and re-gathers
        # the whole stacked cache at the loop boundary
        k_cache = act(k_cache, BATCH, None, "model", None)
        v_cache = act(v_cache, BATCH, None, "model", None)
        out = decode_attention(q, k_cache, v_cache, cur, window=window)
        new_cache = {"k": k_cache, "v": v_cache, "pos": cache["pos"] + 1}
    else:
        if window is not None:
            out = banded_attention(q, k, v, window=window, chunk_q=cfg.chunk_q)
        else:
            out = dot_product_attention(q, k, v, causal=True,
                                        chunk_kv=cfg.chunk_kv)
        new_cache = None
        if cache is not None:  # prefill: fill the cache
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
            new_cache = {"k": k_cache, "v": v_cache, "pos": jnp.asarray(k.shape[1], jnp.int32)}
    out = out.reshape(*x.shape[:-1], h * hd)
    return L.dense_apply(p["o_proj"], out, "attn/o_proj", sp_cfg), new_cache


def _mla_apply(p, x, cfg: AttnConfig, sp_cfg, *, positions, cache, decode,
               per_slot: bool = False):
    """DeepSeek-V2 multi-head latent attention (compressed KV cache)."""
    h = cfg.n_heads
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    dv = cfg.v_head_dim or dn
    lora = cfg.kv_lora
    b = x.shape[0]

    qall = L.dense_apply(p["q_proj"], x, "attn/q_proj", sp_cfg)
    qall = qall.reshape(*x.shape[:-1], h, dn + dr)
    qall = act(qall, BATCH, None, "model", None)  # heads over TP
    q_nope, q_pe = qall[..., :dn], qall[..., dn:]
    q_pe = L.apply_rope(q_pe, positions, cfg.rope_theta)

    down = L.dense_apply(p["kv_down"], x, "attn/kv_down", sp_cfg)
    ckv, k_pe = down[..., :lora], down[..., lora:]
    ckv = L.rmsnorm_apply(p["ckv_norm"], ckv)
    k_pe = L.apply_rope(k_pe[..., None, :], positions, cfg.rope_theta)[..., 0, :]

    if decode:
        assert cache is not None
        if per_slot:
            wpos = jnp.clip(positions[:, -1].astype(jnp.int32), 0,
                            cache["ckv"].shape[1] - 1)
            b_idx = jnp.arange(b)
            ckv_c = cache["ckv"].at[b_idx, wpos].set(
                ckv[:, 0].astype(cache["ckv"].dtype))
            kpe_c = cache["kpe"].at[b_idx, wpos].set(
                k_pe[:, 0].astype(cache["kpe"].dtype))
            cur = positions[:, -1]
        else:
            cur = cache["pos"]
            ckv_c = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv.astype(cache["ckv"].dtype), cur, axis=1)
            kpe_c = jax.lax.dynamic_update_slice_in_dim(cache["kpe"], k_pe.astype(cache["kpe"].dtype), cur, axis=1)
        ckv_c = act(ckv_c, BATCH, None, None)
        kpe_c = act(kpe_c, BATCH, None, None)
        # absorbed-matrix decode: attention entirely in the lora space
        wk = p["k_up"]["w"].reshape(lora, h, dn)
        q_abs = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(jnp.float32),
                           wk.astype(jnp.float32))
        scores = jnp.einsum("bqhl,bsl->bhqs", q_abs, ckv_c.astype(jnp.float32))
        scores += jnp.einsum("bqhd,bsd->bhqs", q_pe.astype(jnp.float32),
                             kpe_c.astype(jnp.float32))
        scores *= (dn + dr) ** -0.5
        smax = ckv_c.shape[1]
        cur_b = jnp.broadcast_to(jnp.asarray(cur), (b,))  # (B,) per-row
        mask = jnp.arange(smax)[None, :] <= cur_b[:, None]
        scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
        attn = jax.nn.softmax(scores, axis=-1)
        ctx_c = jnp.einsum("bhqs,bsl->bqhl", attn, ckv_c.astype(jnp.float32))
        wv = p["v_up"]["w"].reshape(lora, h, dv)
        ctx = jnp.einsum("bqhl,lhv->bqhv", ctx_c, wv.astype(jnp.float32))
        new_cache = {"ckv": ckv_c, "kpe": kpe_c, "pos": cache["pos"] + 1}
    else:
        k_nope = L.dense_apply(p["k_up"], ckv, "attn/k_up", sp_cfg)
        k_nope = k_nope.reshape(*x.shape[:-1], h, dn)
        val = L.dense_apply(p["v_up"], ckv, "attn/v_up", sp_cfg)
        val = val.reshape(*x.shape[:-1], h, dv)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[..., None, :],
                                                      (*k_pe.shape[:-1], h, dr))], axis=-1)
        ctx = dot_product_attention(q, k, val, causal=True,
                                    chunk_kv=cfg.chunk_kv)
        new_cache = None
        if cache is not None:
            ckv_c = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv.astype(cache["ckv"].dtype), 0, axis=1)
            kpe_c = jax.lax.dynamic_update_slice_in_dim(cache["kpe"], k_pe.astype(cache["kpe"].dtype), 0, axis=1)
            new_cache = {"ckv": ckv_c, "kpe": kpe_c,
                         "pos": jnp.asarray(x.shape[1], jnp.int32)}
    ctx = ctx.reshape(*x.shape[:-1], h * dv).astype(x.dtype)
    return L.dense_apply(p["o_proj"], ctx, "attn/o_proj", sp_cfg), new_cache


def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    if cfg.kv_lora is not None:
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora), dtype),
            "kpe": jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv, cfg.head_dim), dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
