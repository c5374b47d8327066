"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, shared experts.

GShard/Switch-style dense dispatch (one-hot einsums) — the formulation
GSPMD turns into all-to-alls under expert-parallel sharding of the
``expert`` logical axis.  Expert FFN weights route through BDWP (the
paper's N:M sparsity applies per-expert along the contraction axes);
the router stays dense (excluded by name — accuracy-critical and tiny,
the spirit of the paper's first-layer exclusion).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import bdwp
from repro.core import operand as O
from repro.core import scopes as SC
from repro.core.sparsity import SparsityConfig
from repro.models import layers as L
from repro.sharding.rules import BATCH, act


def _slot_gather(src, idx):
    """out[g, a, b, :] = src[g, idx[g, a, b], :]; OOB indices read 0.

    Plain take_along_axis.  (A custom-VJP variant with a manual bf16
    scatter-add was tried to keep the backward in 16-bit; under GSPMD
    the explicit scatter replicated the expert-sharded source and
    *tripled* collective traffic — refuted, see EXPERIMENTS.md §Perf.)

    mode="fill" stands in for the zero row a concat-pad would provide:
    gathering from a concat-padded source (sg+1 rows) is miscompiled by
    the SPMD partitioner when the token axis is sharded unevenly (small
    decode batches put the DP axes on sg) — the fill-mode gather from
    the evenly-sharded source is bitwise-identical and partitions
    correctly (tests/test_spmd.py drives this on a forced mesh).
    """
    return jnp.take_along_axis(src[:, None], idx[..., None], axis=2,
                               mode="fill", fill_value=0)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int          # per-expert FFN hidden size
    n_shared: int = 0      # always-on shared experts (deepseek-v2 style)
    capacity_factor: float = 1.25
    group_size: int = 512  # routing group (GShard): capacity is per-group


def moe_init(key, d_model: int, cfg: MoEConfig):
    ks = jax.random.split(key, 8)
    e, dff = cfg.n_experts, cfg.d_expert
    scale = d_model ** -0.5
    p = {
        "router": {"w": jax.random.normal(ks[0], (d_model, e), jnp.float32) * scale},
        "w_gate": jax.random.normal(ks[1], (e, d_model, dff), jnp.float32) * scale,
        "w_up": jax.random.normal(ks[2], (e, d_model, dff), jnp.float32) * scale,
        "w_down": jax.random.normal(ks[3], (e, dff, d_model), jnp.float32) * (dff ** -0.5),
    }
    s = {
        "router": {"w": ("embed", None)},
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }
    if cfg.n_shared:
        sh = cfg.n_shared * dff
        p["shared"] = {
            "w_gate": jax.random.normal(ks[4], (d_model, sh), jnp.float32) * scale,
            "w_up": jax.random.normal(ks[5], (d_model, sh), jnp.float32) * scale,
            "w_down": jax.random.normal(ks[6], (sh, d_model), jnp.float32) * (sh ** -0.5),
        }
        s["shared"] = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                       "w_down": ("mlp", "embed")}
    return p, s


def _nm_mm(leaf, x, name: str, sp_cfg: SparsityConfig, *,
           stacked: bool = False):
    """One bare-leaf matmul through ``operand.nm_apply``.

    Pre-generated operand leaves (the training dataflow — optim/sgd
    wrote the bf16 FF/BP copies at WU time, masks scored once on fp32
    master) consume as PregenOp: the MoE forward/backward derive zero
    masks, packed ``(vals, idx)`` stacks stream through kernels/nm_spmm
    on the pallas backend, and the dense straight-through WU gradient
    rides the BP operand's cotangent — exactly like layers.dense_apply.
    Bare arrays keep the legacy self-masking semantics (MaskedOp:
    serving from raw bf16 weights, dense methods, the pregen=False A/B
    path).  With ``stacked=True`` the leaf carries a leading expert axis
    and the matmul is vmapped per expert — N:M groups stay within one
    expert.
    """
    if isinstance(leaf, O.SparseOperand) or bdwp.is_pregen(leaf):
        op = O.as_operand(leaf, name, sp_cfg)
    else:
        lshape = leaf.shape[1:] if stacked else leaf.shape
        op = O.MaskedOp(leaf, bdwp.pick_cfg(name, lshape, sp_cfg))
    return O.nm_apply(op, x, stacked=stacked)


def _expert_ffn(w_gate, w_up, w_down, x, sp_cfg: SparsityConfig):
    """x: (E, C, d) -> (E, C, d); vmapped BDWP matmuls per expert."""
    h = L.swiglu(_nm_mm(w_gate, x, "moe/expert/w_gate", sp_cfg, stacked=True),
                 _nm_mm(w_up, x, "moe/expert/w_up", sp_cfg, stacked=True))
    return _nm_mm(w_down, h.astype(x.dtype), "moe/expert/w_down", sp_cfg,
                  stacked=True)


def moe_apply(p, x, cfg: MoEConfig, sp_cfg: SparsityConfig):
    """x: (B, S, d) -> (B, S, d) plus aux load-balancing loss.

    GShard-style *grouped* routing with gather/scatter dispatch: tokens
    are split into groups of ``group_size`` and capacity is per-group,
    so no tensor ever scales with (global_tokens x experts x capacity).
    Dispatch/combine are index gathers (memory ops, fully differentiable
    through the value path), not dense one-hot matmuls — at the 1M-token
    train_4k shapes the one-hot formulation would cost more FLOPs than
    the experts themselves.  Expert-parallel sharding over "model" turns
    the (G, E, C, d) regroup into the canonical all-to-all.
    """
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    sg = min(cfg.group_size, t)
    while t % sg:  # static: largest divisor fallback
        sg -= 1
    g = t // sg
    cap = int(max(cfg.top_k, round(sg * cfg.capacity_factor * k / e)))
    cap = min(cap, sg)

    with jax.named_scope(SC.MOE_DISPATCH):
        xt = x.reshape(g, sg, d)
        logits = jnp.matmul(xt, p["router"]["w"].astype(xt.dtype),
                            preferred_element_type=jnp.float32)  # (G, S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)  # (G, S, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)

        # slot assignment inside each (group, expert) queue
        onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)  # (G, S, K, E)
        flat = onehot.reshape(g, sg * k, e)
        pos_in_e = jnp.cumsum(flat, axis=1) - flat              # (G, S*K, E)
        pos = (pos_in_e * flat).sum(-1).reshape(g, sg, k)       # (G, S, K)
        keep = pos < cap
        gate_vals = gate_vals * keep

        # scatter: slot_token[g, e, c] = index of the token filling that slot
        gi = jnp.broadcast_to(jnp.arange(g)[:, None, None], gate_idx.shape)
        si = jnp.broadcast_to(jnp.arange(sg)[None, :, None], gate_idx.shape)
        pos_c = jnp.where(keep, pos, cap)  # dropped -> sentinel column
        slot_token = jnp.full((g, e, cap + 1), sg, jnp.int32)  # sg = zero row
        slot_token = slot_token.at[gi, gate_idx, pos_c].set(si, mode="drop")
        slot_token = slot_token[..., :cap]                      # (G, E, C)

        # gather dispatched tokens (sentinel index sg is OOB -> reads zero)
        x_e = _slot_gather(xt, slot_token)                      # (G, E, C, d)
        x_e = act(x_e, BATCH, "model", None, None)  # EP: experts over "model"
        xe2 = x_e.transpose(1, 0, 2, 3).reshape(e, g * cap, d)  # the all-to-all
    y_e = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"], xe2, sp_cfg)
    with jax.named_scope(SC.MOE_DISPATCH):
        y_e = y_e.reshape(e, g, cap, d).transpose(1, 0, 2, 3)   # (G, E, C, d)
        y_e = act(y_e, BATCH, "model", None, None)
        # reshard expert-sharded outputs back to token shards BEFORE the
        # combine gather — one (G,E,C,d)-sized hop (a2a-class traffic);
        # gathering from an expert-sharded tensor instead would all-gather
        # the full dispatched tensor onto every chip (~16x the bytes)
        y_e = act(y_e, BATCH, None, None, None)

        # combine: token side gathers its K slots back, weighted by gates
        y_flat = y_e.reshape(g, e * cap, d)
        slot_of = gate_idx * cap + jnp.where(keep, pos, 0)      # (G, S, K)
        y_k = _slot_gather(y_flat, slot_of)                     # (G, S, K, d)
        yt = (y_k * gate_vals[..., None].astype(y_k.dtype)).sum(2)  # (G, S, d)
        yt = act(yt, BATCH, None, None)
        yt = yt.reshape(t, d)

    if "shared" in p:
        sh = p["shared"]
        xt2 = xt.reshape(t, d)
        h = L.swiglu(_nm_mm(sh["w_gate"], xt2, "moe/shared/w_gate", sp_cfg),
                     _nm_mm(sh["w_up"], xt2, "moe/shared/w_up", sp_cfg))
        yt = yt + _nm_mm(sh["w_down"], h.astype(xt2.dtype),
                         "moe/shared/w_down", sp_cfg)

    # Switch-style load-balance aux loss (counts from kept assignments)
    with jax.named_scope(SC.MOE_DISPATCH):
        me = probs.mean((0, 1))                                 # (E,)
        counts = (onehot * keep[..., None]).sum((0, 1, 2)).astype(jnp.float32)
        ce = counts / jnp.maximum(counts.sum(), 1.0)
        aux = e * jnp.sum(me * ce)
    return yt.reshape(b, s, d).astype(x.dtype), aux
