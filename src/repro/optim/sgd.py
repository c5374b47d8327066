"""WUVE analogue: mixed-precision momentum SGD with SR-STE decay and
N:M sparse weight *pre-generation* (paper Fig. 11c) — executed for real.

State per parameter:
  master   fp32  (sharded like the param)
  momentum fp32
plus the *pre-generated compute tree* emitted by every update — the
dataflow the paper fuses into WUVE+SORE: at WU time the optimizer
computes each prunable weight's FF and BP N:M masks ONCE from fp32
master (a single fused ``lax.top_k`` per parameter — nm_mask_pair),
applies SR-STE's sparse-refined decay from the *same* masks (the copy
stored at the previous WU), and writes the bf16 FF/BP operands — pruned
copies, or SORE-packed ``(vals, idx)`` where eligible — that the next
iteration's FF and BP load directly (core/operand.nm_apply over
PregenOp leaves).
Forward passes never touch fp32 and never re-derive a mask: the lowered
train step carries exactly one top_k/sort selection per prunable
parameter (down from one per consumer — FF forward, FF remat recompute,
BP backward and SR-STE decay each re-derived it: 4x measured in
benchmarks/pregen_bench.py), and the FF/BP/decay masks can no longer
disagree at bf16-rounding near-ties.

The fused Pallas kernel (kernels/fused_update.py) implements the FF lane
of the same math per VMEM tile for the TPU deployment path and is wired
in via ``use_pallas=True`` (srste/bdwp, element granularity); this
module's jnp formulation lowers cleanly in the dry-run with identical
semantics — tests/test_pregen.py pins the two paths together bitwise.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bdwp
from repro.core import operand as O
from repro.core import scopes as S
from repro.core.sparsity import (SparsityConfig, _move_axis_last, nm_mask,
                                 nm_mask_pair, nm_mask_shared,
                                 nm_mask_transposable, nm_pack_from_mask,
                                 nm_unpack_n)


@dataclasses.dataclass(frozen=True)
class SGDConfig:
    lr: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 5e-4
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_frac: float = 0.01


def lr_schedule(cfg: SGDConfig, step):
    step = step.astype(jnp.float32)
    warm = cfg.lr * step / max(cfg.warmup_steps, 1)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = cfg.min_lr_frac * cfg.lr + (1 - cfg.min_lr_frac) * cfg.lr \
        * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < cfg.warmup_steps, warm, cos)


def init_state(params):
    return {
        "master": jax.tree.map(lambda p: p.astype(jnp.float32), params),
        "momentum": jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params),
        "step": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# Pre-generation: master fp32 -> the bf16 compute tree FF/BP consume
# ---------------------------------------------------------------------------


def _pregen_masks(w, sp_cfg: SparsityConfig):
    """(ff_mask, bp_mask, decay_mask) of one fp32 weight — the masks the
    next step's FF/BP and this step's successor-decay all share.  Element
    granularity fuses the FF+BP selections into ONE top_k (nm_mask_pair);
    unused directions return None."""
    n, m = sp_cfg.n, sp_cfg.m
    ff_ax, bp_ax = w.ndim - 2, w.ndim - 1
    if sp_cfg.transposable:
        # ONE mask, N:M along both the contraction and the output axis
        # (arXiv 2102.08124) — serves FF, BP and the SR-STE decay, so
        # the per-param mask state halves
        tm = nm_mask_transposable(w, n, m)
        return tm, tm, tm
    shared = sp_cfg.granularity == "shared"
    ff_mask = bp_mask = None
    if sp_cfg.prunes_ff_weights() and sp_cfg.prunes_bp_weights():
        if shared:
            ff_mask = nm_mask_shared(w, n, m, ff_ax, bp_ax, sp_cfg.tile)
            bp_mask = nm_mask_shared(w, n, m, bp_ax, ff_ax, sp_cfg.tile)
        else:
            ff_mask, bp_mask = nm_mask_pair(w, n, m, ff_ax, bp_ax)
    elif sp_cfg.prunes_ff_weights():
        ff_mask = nm_mask_shared(w, n, m, ff_ax, bp_ax, sp_cfg.tile) \
            if shared else nm_mask(w, n, m, axis=ff_ax)
    elif sp_cfg.prunes_bp_weights():
        bp_mask = nm_mask_shared(w, n, m, bp_ax, ff_ax, sp_cfg.tile) \
            if shared else nm_mask(w, n, m, axis=bp_ax)
    decay_mask = bp_mask if sp_cfg.method == "sdwp" else ff_mask
    return ff_mask, bp_mask, decay_mask


def _pregen_leaf(w, sp_cfg: SparsityConfig, pack: bool) -> O.PregenOp:
    """fp32 weight -> PregenOp{ff | (vals, idx), bp, mask} operand leaf.

    Masking commutes with the bf16 cast (cast(0) == 0), so the pruned
    bf16 operands equal what masking the bf16 copy would give — but the
    *selection* is scored on fp32 master, fixing the bf16/fp32 mask-source
    split between FF/BP and SR-STE decay.
    """
    ff_mask, bp_mask, decay_mask = _pregen_masks(w, sp_cfg)
    bp = jnp.where(bp_mask, w, 0.0) if bp_mask is not None else w
    if sp_cfg.transposable:
        # the one transposable-masked operand serves FF and BP — no
        # separate ff leaf (bf16 weight state halves); pack rides the
        # same mask along the contraction axis
        bp16 = bp.astype(jnp.bfloat16)
        if pack:
            vals, idx = nm_pack_from_mask(bp16, ff_mask, sp_cfg.n, sp_cfg.m,
                                          axis=w.ndim - 2)
            return O.PregenOp(bp=bp16, vals=vals, idx=idx, mask=decay_mask,
                              cfg=sp_cfg, idx_bits=8)
        return O.PregenOp(bp=bp16, mask=decay_mask, cfg=sp_cfg)
    ff = jnp.where(ff_mask, w, 0.0) if ff_mask is not None else w
    ff16 = ff.astype(jnp.bfloat16)
    if pack and ff_mask is not None and sp_cfg.granularity == "element":
        # SORE packing along the contraction axis, sort-free from the mask
        vals, idx = nm_pack_from_mask(ff16, ff_mask, sp_cfg.n, sp_cfg.m,
                                      axis=w.ndim - 2)
        return O.PregenOp(bp=bp.astype(jnp.bfloat16), vals=vals, idx=idx,
                          mask=decay_mask, cfg=sp_cfg, idx_bits=8)
    return O.PregenOp(bp=bp.astype(jnp.bfloat16), ff=ff16, mask=decay_mask,
                      cfg=sp_cfg)


def pregen_tree(master, sp_cfg: Optional[SparsityConfig], *,
                pack: bool = False, bare_sites: bool = True):
    """Build the full pre-generated compute tree from fp32 master.

    Prunable weights (bdwp.pregen_site) become PregenOp leaves — both the
    ``{"w": ...}`` leaf-dict sites and the bare-array MoE expert stacks
    (masks per expert along the last-two contraction/output axes, one
    fused ``nm_mask_pair`` over the whole stacked leaf); every other
    leaf becomes its plain bf16 compute copy.  Used to bootstrap
    ``init_train_state``, to upgrade pre-pregen checkpoints, and
    abstractly (under eval_shape) by the step builders and dry-run.
    ``bare_sites=False`` reproduces the pre-MoE structure (dict sites
    only) so restore_with_pregen can recognize older checkpoints.
    """
    from repro.core.sparsity import DENSE

    sp = sp_cfg if sp_cfg is not None else DENSE

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        name = "/".join(path)
        lshape, _ = _logical_shape(name, node.shape)
        if bdwp.pregen_site(name, lshape, sp, bare=bare_sites):
            return _pregen_leaf(node.astype(jnp.float32), sp, pack)
        if jnp.issubdtype(node.dtype, jnp.floating):
            return node.astype(jnp.bfloat16)
        return node

    return walk(master, ())


def pregen_grads(grads_compute):
    """Cotangents of the compute tree -> master-shaped gradient tree.

    The pregen custom VJPs put the dense straight-through WU gradient on
    the BP operand (always dense-shaped); everything else maps through.
    """
    def walk(node):
        if bdwp.is_pregen(node):  # PregenOp or legacy operand dict
            return node["bp"]
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node

    return walk(grads_compute)


def stored_decay_masks(compute) -> dict:
    """{master leaf name: decay mask} from a pre-generated compute tree."""
    out = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            if bdwp.is_pregen(v):  # PregenOp or legacy operand dict
                if v.get("mask") is not None:
                    out["/".join(path + (k,))] = v["mask"]
            elif isinstance(v, dict):
                walk(v, path + (k,))

    walk(compute, ())
    return out


# ---------------------------------------------------------------------------
# The update
# ---------------------------------------------------------------------------


def update(state, grads, opt_cfg: SGDConfig, sp_cfg: SparsityConfig,
           param_names=None, *, prev_compute=None, pregen: bool = False,
           pack: bool = False, use_pallas: bool = False):
    """One optimizer step. Returns (new_state, compute_tree).

    pregen=False (legacy / standalone callers): the SR-STE decay mask is
    re-derived from fp32 master and the returned compute tree is the
    plain bf16 cast of the new master.

    pregen=True (the train-step dataflow): the decay mask is the one
    STORED at the previous WU (``prev_compute`` — same mask FF/BP just
    consumed), and the returned compute tree is the next step's
    pre-generated operands — each prunable param pays exactly one fused
    top_k, in this function, and nowhere else in the step.

    use_pallas=True routes eligible leaves (srste/bdwp weight updates,
    element granularity) through the fused WUVE+SORE Pallas kernel
    (kernels/fused_update): in-VMEM decay mask + momentum update + FF
    pack in one pass; the BP operand is derived jnp-side.  Bitwise
    identical to the jnp path.
    """
    names = param_names or _names_of(state["master"])
    prev_masks = stored_decay_masks(prev_compute) if (
        pregen and prev_compute is not None) else {}

    def jnp_upd(name, w, g, v, lshape, off, site):
        g = g.astype(jnp.float32)
        g = g + opt_cfg.weight_decay * w
        if (not sp_cfg.is_dense and sp_cfg.lam > 0.0
                and bdwp.decays(name, lshape, sp_cfg)
                and sp_cfg.method in ("srste", "bdwp", "sdwp")):
            mask = prev_masks.get(name)
            if mask is None:  # legacy / non-pregen leaf: re-derive from master
                axis = (bdwp.bp_group_axis(lshape) if sp_cfg.method == "sdwp"
                        else bdwp.ff_group_axis(lshape)) + off
                mask = nm_mask(w, sp_cfg.n, sp_cfg.m, axis=axis)
            g = g + sp_cfg.lam * jnp.where(mask, 0.0, w)
        v_new = opt_cfg.momentum * v + g
        w_new = w - lr * v_new
        if pregen and site:
            comp = _pregen_leaf(w_new, sp_cfg, pack)
        else:
            comp = w_new.astype(jnp.bfloat16)
        return w_new, v_new, comp

    def pallas_upd(name, w, g, v):
        """Fused WUVE+SORE kernel on the FF lane: move the contraction
        axis last, one kernel pass updates w/v (decay mask re-derived
        in-VMEM from fp32 master — identical to the stored mask) and
        emits the packed FF operand; BP operand derived jnp-side."""
        from repro.kernels import ops

        ff_ax = w.ndim - 2
        w_t, inv = _move_axis_last(w, ff_ax)
        g_t, _ = _move_axis_last(g.astype(jnp.float32), ff_ax)
        v_t, _ = _move_axis_last(v, ff_ax)
        shp = w_t.shape
        nw, nv, pv, pi = ops.fused_update(
            w_t.reshape(-1, shp[-1]), g_t.reshape(-1, shp[-1]),
            v_t.reshape(-1, shp[-1]), lr, opt_cfg.momentum,
            opt_cfg.weight_decay, sp_cfg.lam, sp_cfg.n, sp_cfg.m)
        kc = shp[-1] // sp_cfg.m * sp_cfg.n
        w_new = jnp.transpose(nw.reshape(shp), inv)
        v_new = jnp.transpose(nv.reshape(shp), inv)
        vals = jnp.transpose(pv.reshape(*shp[:-1], kc), inv)
        idx = jnp.transpose(pi.reshape(*shp[:-1], kc), inv)
        ff_mask = nm_unpack_n(jnp.ones_like(vals, dtype=bool), idx,
                              sp_cfg.n, sp_cfg.m, axis=ff_ax)
        if sp_cfg.prunes_bp_weights():  # bdwp: BP operand jnp-side
            bp_mask = nm_mask(w_new, sp_cfg.n, sp_cfg.m, axis=w.ndim - 1)
            bp_op = jnp.where(bp_mask, w_new, 0.0).astype(jnp.bfloat16)
        else:  # srste: BP runs dense
            bp_op = w_new.astype(jnp.bfloat16)
        if pack and sp_cfg.granularity == "element":
            leaf = O.PregenOp(bp=bp_op, vals=vals, idx=idx, mask=ff_mask,
                              cfg=sp_cfg, idx_bits=8)
        else:
            leaf = O.PregenOp(bp=bp_op, mask=ff_mask, cfg=sp_cfg,
                              ff=nm_unpack_n(vals, idx, sp_cfg.n, sp_cfg.m,
                                             axis=ff_ax))
        return w_new, v_new, leaf

    def upd(name, w, g, v):
        lshape, off = _logical_shape(name, w.shape)
        site = pregen and bdwp.pregen_site(name, lshape, sp_cfg)
        if (site and use_pallas and sp_cfg.granularity == "element"
                and sp_cfg.method in ("srste", "bdwp")
                and not sp_cfg.transposable):
            # fused_update derives a one-sided FF mask in-VMEM — wrong
            # for transposable operands, which stay on the jnp path
            return pallas_upd(name, w, g, v)
        return jnp_upd(name, w, g, v, lshape, off, site)

    flat_w, tdef = jax.tree_util.tree_flatten(state["master"])
    flat_g = jax.tree_util.tree_flatten(grads)[0]
    flat_v = jax.tree_util.tree_flatten(state["momentum"])[0]
    with jax.named_scope(S.UPDATE):
        lr = lr_schedule(opt_cfg, state["step"])  # jnp_upd, pallas_upd read it
        outs = [upd(n, w, g, v)
                for n, w, g, v in zip(names, flat_w, flat_g, flat_v)]
        step = state["step"] + 1
    new_master = jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs])
    new_mom = jax.tree_util.tree_unflatten(tdef, [o[1] for o in outs])
    # pre-generation: the compute operands written at WU time (Fig. 11c);
    # PregenOp "leaves" ride through unflatten as opaque pytree subtrees
    compute = jax.tree_util.tree_unflatten(tdef, [o[2] for o in outs])
    new_state = {"master": new_master, "momentum": new_mom, "step": step}
    return new_state, compute


_STACKED_PREFIXES = ("blocks/", "enc_blocks/", "dec_blocks/")


def _logical_shape(name: str, shape):
    """Per-layer shape as the model sees it: scanned param trees carry a
    leading 'layer' axis that must not count as a contraction axis."""
    if any(name.startswith(p) or f"/{p}" in name for p in _STACKED_PREFIXES):
        return tuple(shape[1:]), 1
    return tuple(shape), 0


def _names_of(tree):
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in paths]
