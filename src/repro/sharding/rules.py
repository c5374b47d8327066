"""Logical-axis -> mesh-axis rule tables (MaxText-style GSPMD planning).

One model definition + one spec tree serve every (shape x mesh) cell:
the rule table chosen per workload maps each logical axis to mesh axes.

Workloads:
  TRAIN       — FSDP("data") x TP("model"); pure DP across "pod"
                (hierarchical: params replicated across pods, weight
                all-gathers stay intra-pod, grad sync crosses pods once).
  SERVE_BATCH — prefill/decode with real batch: TP("model") weights
                (replicated over "data" — no per-step FSDP gathers),
                batch over ("pod","data"), KV cache sequence over "model"?
                no — cache follows batch; attention stays local.
  SERVE_LONG  — batch=1, 500k context: weights TP("model"), the KV/global
                cache sequence-sharded over "data" => distributed
                flash-decoding (partial softmax + small all-reduces).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple, or None=replicated)
TRAIN_RULES = {
    "embed": "data",      # FSDP: shard the width axis of every weight
    "mlp": "model",       # Megatron TP
    "heads": "model",
    "kv": "model",
    "vocab": "model",
    "expert": "model",    # expert parallelism
    "layer": None,
}

SERVE_BATCH_RULES = {
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "kv": "model",
    "vocab": "model",
    "expert": "model",
    "layer": None,
}

SERVE_LONG_RULES = dict(SERVE_BATCH_RULES)


def rules_for(shape_kind: str):
    if shape_kind == "train":
        return TRAIN_RULES
    return SERVE_BATCH_RULES


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def spec_to_pspec(axes: tuple, rules: dict, shape=None,
                  mesh: Optional[Mesh] = None,
                  group_multiples: Optional[dict] = None) -> P:
    """Logical axes -> PartitionSpec with three production guards:

    * dedupe — a mesh axis may appear once per spec (stacked MoE weights
      map both "expert" and "mlp" to "model": first occurrence wins,
      later ones fall back to replicated);
    * divisibility — with ``shape`` + ``mesh`` given, any dim the mesh
      axis doesn't divide evenly is replicated instead (e.g. hymba's
      fused ssm in_proj output of 6482);
    * group integrity — ``group_multiples[i]`` (dim index -> int) demands
      the *per-shard* size of dim ``i`` stay a multiple of that value;
      a mesh axis that would cut a group is dropped (replicated).  This
      is how N:M structure is expressed to the partitioner: groups of
      size M along a grouped weight axis — or runs of N along a packed
      compact axis — must never straddle a "model" shard boundary.
    """
    entries, used = [], set()
    for i, ax in enumerate(axes):
        target = rules.get(ax) if ax is not None else None
        if target is not None:
            tgt_axes = target if isinstance(target, tuple) else (target,)
            if any(t in used for t in tgt_axes):
                target = None
            elif shape is not None and mesh is not None:
                size = 1
                for t in tgt_axes:
                    size *= mesh.shape.get(t, 1)
                mult = (group_multiples or {}).get(i, 1)
                if shape[i] % size or (shape[i] // size) % mult:
                    target = None
            if target is not None:
                used.update(tgt_axes)
        entries.append(target)
    return P(*entries)


def params_pspecs(specs_tree, rules: dict, params=None,
                  mesh: Optional[Mesh] = None):
    """Map a logical-axis spec tree to a PartitionSpec tree.

    params (optional): matching tree of arrays/ShapeDtypeStructs enabling
    the divisibility fallback; mesh required alongside."""
    if params is None:
        return jax.tree.map(lambda ax: spec_to_pspec(ax, rules), specs_tree,
                            is_leaf=_is_axes)
    flat_s, tdef = jax.tree_util.tree_flatten(specs_tree, is_leaf=_is_axes)
    flat_p = jax.tree_util.tree_flatten(params)[0]
    out = [spec_to_pspec(ax, rules, shape=tuple(p.shape), mesh=mesh)
           for ax, p in zip(flat_s, flat_p)]
    return jax.tree_util.tree_unflatten(tdef, out)


def params_shardings(specs_tree, mesh: Mesh, rules: dict, params=None):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps),
                        params_pspecs(specs_tree, rules, params, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# N:M group integrity
# ---------------------------------------------------------------------------
#
# BDWP prunes in groups of M along a weight's contraction axis (axis
# ndim-2 of every ``{"w": ...}`` leaf-dict), and the packed serving
# format stores the N survivors of each group contiguously along the
# compact axis.  A shard boundary inside a group would make the group's
# top-N selection (training) or its (vals, idx) run (serving) straddle
# two devices — the rules must never emit such a spec, and the resolved
# shardings are asserted against it.


def _shard_count(entry, mesh: Mesh) -> int:
    if entry is None:
        return 1
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return size


def nm_group_multiples(name: str, shape, sp_cfg) -> Optional[dict]:
    """Per-dim per-shard multiples an N:M-prunable weight demands.

    BDWP tiles M-groups along the FF/contraction axis (ndim-2) AND the
    BP/output axis (ndim-1); one-directional methods constrain only
    their own axis.  None for dense / non-prunable leaves.
    """
    if sp_cfg is None or getattr(sp_cfg, "is_dense", True):
        return None
    from repro.core import bdwp
    if len(shape) < 2 or not bdwp.should_prune(name, tuple(shape[-2:]),
                                               sp_cfg):
        return None
    gm = {}
    if sp_cfg.prunes_ff_weights():
        gm[len(shape) - 2] = sp_cfg.m
    if sp_cfg.prunes_bp_weights() or sp_cfg.prunes_bp_grads():
        gm[len(shape) - 1] = sp_cfg.m
    return gm or {len(shape) - 2: sp_cfg.m}


def nm_params_pspecs(specs_tree, rules: dict, params, mesh: Mesh,
                     sp_cfg=None):
    """``params_pspecs`` plus the N:M group guard.

    Every prunable leaf — a ``{"w": ...}`` leaf-dict (``bdwp.
    should_prune`` on its tree path) or a bare-array expert stack
    (``bdwp.bare_nm_leaf``: MoE w_gate/w_up/w_down, groups along the
    last two axes *within* each expert) — carries ``nm_group_multiples``
    into ``spec_to_pspec`` so a mesh axis that would split an M-group
    falls back to replicated; expert-parallel sharding of the leading
    expert axis is untouched (a whole expert per shard never cuts a
    group).  With ``sp_cfg`` None or dense this degenerates to
    ``params_pspecs``.
    """
    if sp_cfg is None or getattr(sp_cfg, "is_dense", True):
        return params_pspecs(specs_tree, rules, params, mesh)
    from repro.core import bdwp

    def walk(spec_node, p_node, path):
        if isinstance(spec_node, dict):
            if "w" in spec_node and _is_axes(spec_node["w"]):
                name = "/".join(str(k) for k in path)
                out = {}
                for key, ax in spec_node.items():
                    shape = tuple(p_node[key].shape)
                    gm = (nm_group_multiples(name, shape, sp_cfg)
                          if key == "w" else None)
                    out[key] = spec_to_pspec(ax, rules, shape=shape,
                                             mesh=mesh, group_multiples=gm)
                return out
            return {k: walk(v, p_node[k], path + (k,))
                    for k, v in spec_node.items()}
        name = "/".join(str(k) for k in path)
        shape = tuple(p_node.shape)
        gm = nm_group_multiples(name, shape, sp_cfg) \
            if bdwp.bare_nm_leaf(name) else None
        return spec_to_pspec(spec_node, rules, shape=shape, mesh=mesh,
                             group_multiples=gm)

    return walk(specs_tree, params, ())


def pregen_pspecs(compute_tree, master_pspecs):
    """PartitionSpecs for a pre-generated compute tree (optim/sgd).

    The compute tree mirrors master except that prunable weights —
    ``{"w": ...}`` dict sites and bare-array MoE expert stacks alike —
    became ``operand.PregenOp`` leaves ({ff | (vals, idx), bp, mask}).
    Every operand child inherits the master weight's spec: ff/bp/mask
    are dense-shaped (expert-parallel sharding of a stacked leaf carries
    straight over), and the packed vals/idx only shrink the contraction
    dim (ndim-2) by n/m — a mesh axis the group guard admitted for w
    (per-shard multiple of M along K) divides Kc with per-shard runs
    whole multiples of N, so the same spec keeps packed runs group-whole
    under SPMD (``assert_nm_unsplit`` re-checks).
    """
    from repro.core import bdwp, operand as O

    def walk(c, s):
        if isinstance(c, O.SparseOperand):
            return c.map_children(lambda _: s)
        if bdwp.is_pregen(c):  # legacy operand dicts
            return {k: s for k in c}
        if isinstance(c, dict):
            return {k: walk(v, s[k]) for k, v in c.items()}
        return s

    return walk(compute_tree, master_pspecs)


def assert_nm_unsplit(pspecs_tree, params_tree, mesh: Mesh, sp_cfg) -> None:
    """Assert no resolved sharding splits an N:M group.

    Dense prunable ``w`` leaves must keep per-shard size a multiple of M
    along every grouped axis (``nm_group_multiples``); element-packed
    ``vals``/``idx`` leaves a multiple of N along the compact axis
    (ndim-2).  Operand nodes (``operand.PregenOp`` compute leaves,
    ``operand.PackedOp`` serving leaves) are recognized by type; the
    equivalent legacy dict layouts keep working.  Raises AssertionError
    naming the offending leaf.  The pspec tree may hold PartitionSpecs
    or NamedShardings.
    """
    if sp_cfg is None or getattr(sp_cfg, "is_dense", True):
        return
    from repro.core import operand as O

    def as_spec(x) -> P:
        return x.spec if isinstance(x, NamedSharding) else x

    def check(name, key, spec, shape, multiples: dict):
        for axis, multiple in multiples.items():
            entry = spec[axis] if axis < len(spec) else None
            shards = _shard_count(entry, mesh)
            if shape[axis] % shards or (shape[axis] // shards) % multiple:
                raise AssertionError(
                    f"N:M group split: {name}/{key} dim {axis} (size "
                    f"{shape[axis]}) sharded {shards}-way over {entry!r} — "
                    f"per-shard size must be a multiple of {multiple}")

    def is_spec(x):
        return isinstance(x, (P, NamedSharding))

    def idx_multiple(spec_node, key) -> int:
        """Per-shard multiple for a compact-axis index plane.  Byte-wide
        idx shards like vals (whole N-runs).  A u4 plane holds two
        offsets per byte: even N needs N/2 bytes per group; odd N's
        group boundaries fall mid-byte, so shards must cover whole
        byte-aligned group pairs (N bytes = 2 groups)."""
        if key == "idx" and getattr(spec_node, "idx_bits", 8) == 4:
            return sp_cfg.n // 2 if sp_cfg.n % 2 == 0 else sp_cfg.n
        return sp_cfg.n

    def check_pregen(name, spec_node, p_node):
        """PregenOp (or legacy operand-dict) site: pruned operands carry
        M-groups on their own axis; packed vals/idx carry N-runs on the
        compact axis (ndim-2)."""
        if sp_cfg.prunes_ff_weights():
            if "ff" in spec_node and is_spec(spec_node["ff"]):
                shape = tuple(p_node["ff"].shape)
                check(name, "ff", as_spec(spec_node["ff"]), shape,
                      {len(shape) - 2: sp_cfg.m})
            for key in ("vals", "idx"):
                if key in spec_node and is_spec(spec_node[key]):
                    shape = tuple(p_node[key].shape)
                    check(name, key, as_spec(spec_node[key]), shape,
                          {len(shape) - 2: idx_multiple(spec_node, key)})
        if sp_cfg.prunes_bp_weights() and is_spec(spec_node["bp"]):
            shape = tuple(p_node["bp"].shape)
            check(name, "bp", as_spec(spec_node["bp"]), shape,
                  {len(shape) - 1: sp_cfg.m})

    def walk(spec_node, p_node, path):
        if isinstance(spec_node, O.PregenOp):
            check_pregen("/".join(str(k) for k in path), spec_node, p_node)
            return
        if isinstance(spec_node, O.PackedOp):
            # element-packed serving operand: N-runs on the compact axis
            # (N/2-byte runs on a u4 index plane)
            name = "/".join(str(k) for k in path)
            for key in ("vals", "idx"):
                if is_spec(spec_node[key]):
                    shape = tuple(p_node[key].shape)
                    check(name, key, as_spec(spec_node[key]), shape,
                          {len(shape) - 2: idx_multiple(spec_node, key)})
            return
        if isinstance(spec_node, O.SharedOp):
            # shared-mode: vals carry the compact axis; per-row idx has
            # no N-run constraint
            name = "/".join(str(k) for k in path)
            if is_spec(spec_node["vals"]):
                shape = tuple(p_node["vals"].shape)
                if len(shape) >= 2:
                    check(name, "vals", as_spec(spec_node["vals"]), shape,
                          {len(shape) - 2: sp_cfg.n})
            return
        if is_spec(spec_node):
            # bare-array leaf (MoE expert stack / shared-expert mat):
            # M-groups on the last two axes within each expert, and the
            # leading expert/layer axes must shard evenly — an expert's
            # matrix never straddles devices
            from repro.core import bdwp
            name = "/".join(str(k) for k in path)
            gm = nm_group_multiples(name, tuple(p_node.shape), sp_cfg) \
                if bdwp.bare_nm_leaf(name) else None
            if gm:
                shape = tuple(p_node.shape)
                for i in range(len(shape) - 2):
                    gm.setdefault(i, 1)
                check(name, "leaf", as_spec(spec_node), shape, gm)
            return
        if isinstance(spec_node, dict):
            name = "/".join(str(k) for k in path)
            if "bp" in spec_node and ("ff" in spec_node
                                      or "vals" in spec_node):
                # legacy pre-generated operand dict (pre-operand era)
                check_pregen(name, spec_node, p_node)
                return
            if "w" in spec_node and is_spec(spec_node["w"]):
                shape = tuple(p_node["w"].shape)
                gm = nm_group_multiples(name, shape, sp_cfg)
                if gm:
                    check(name, "w", as_spec(spec_node["w"]), shape, gm)
                return
            if "vals" in spec_node and is_spec(spec_node["vals"]):
                v_rank = len(p_node["vals"].shape)
                for key in ("vals", "idx"):
                    # shared-mode idx (rank vals-1) has no compact axis
                    if key in spec_node and is_spec(spec_node[key]) \
                            and len(p_node[key].shape) == v_rank >= 2:
                        shape = tuple(p_node[key].shape)
                        check(name, key, as_spec(spec_node[key]),
                              shape, {len(shape) - 2: sp_cfg.n})
                return
            for k, v in spec_node.items():
                walk(v, p_node[k], path + (k,))

    walk(pspecs_tree, params_tree, ())


def grad_sync_pspecs(mesh: Mesh) -> dict:
    """PartitionSpecs for the bucketed compressed gradient sync.

    err: the persistent error-feedback residual, (n_pods, T_loc*S) —
    row p lives on pod p's devices and the width axis is laid out as S
    device-local slabs along the intra-pod axes, so each device's EF
    state covers exactly the leaf blocks it compresses
    (optim/compress._slab_layout) and never moves between steps.  On a
    pod-less mesh the spec degenerates to replicated (the sync path is
    a no-op there).
    """
    pod = "pod" if "pod" in mesh.axis_names else None
    intra = tuple(a for a in mesh.axis_names if a != "pod")
    slab = P(pod, intra) if intra else P(pod, None)
    return {"err": slab}


def batch_axes(mesh: Mesh):
    """DP axes for the activation batch dimension on this mesh."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


# ---------------------------------------------------------------------------
# Input / cache PartitionSpecs per workload
# ---------------------------------------------------------------------------


def train_input_pspecs(input_specs: dict, mesh: Mesh):
    dp = batch_axes(mesh)
    out = {}
    for name, leaf in input_specs.items():
        if name in ("tokens", "labels"):
            out[name] = P(dp, None)
        elif name in ("frames", "prefix_embeds"):
            out[name] = P(dp, None, None)
        else:
            out[name] = P()
    return out


def serve_input_pspecs(input_specs: dict, mesh: Mesh, *, long_context: bool):
    """decode/prefill inputs; caches handled leaf-by-leaf by rank/name."""
    dp = batch_axes(mesh)
    bp = None if long_context else dp

    tp = mesh.shape.get("model", 1)

    def cache_spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        rank = len(leaf.shape)
        # rank discriminates stacked (leading scan-layer dim) vs the
        # unstacked prelude cache (deepseek's dense first layer)
        if name in ("k", "v"):  # (L, B, S, Hkv, D) or (B, S, Hkv, D)
            seq_ax = "data" if long_context else None
            head_ax = "model" if leaf.shape[rank - 2] % tp == 0 else None
            tail = (bp, seq_ax, head_ax, None)
            return P(*(((None,) + tail) if rank == 5 else tail))
        if name in ("ckv", "kpe"):  # (L, B, S, dim) or (B, S, dim)
            seq_ax = "data" if long_context else None
            tail = (bp, seq_ax, None)
            return P(*(((None,) + tail) if rank == 4 else tail))
        if name == "state":  # (L, B, H, N, Pd) or (B, H, N, Pd)
            head_ax = "model" if leaf.shape[rank - 3] % tp == 0 else None
            tail = (bp, head_ax, None, None)
            return P(*(((None,) + tail) if rank == 5 else tail))
        if name == "conv":  # (L, B, K-1, C) or (B, K-1, C)
            ch_ax = "model" if leaf.shape[rank - 1] % tp == 0 else None
            tail = (bp, None, ch_ax)
            return P(*(((None,) + tail) if rank == 4 else tail))
        if name == "pos":
            return P() if rank == 0 else P(None)
        return P(*([None] * rank))

    out = {}
    for name, leaf in input_specs.items():
        if name == "cache":
            out[name] = jax.tree_util.tree_map_with_path(cache_spec, leaf)
        elif name == "token":
            out[name] = P(bp, None)
        elif name == "tokens":
            out[name] = P(bp, None)
        elif name in ("frames", "prefix_embeds", "enc_out"):
            out[name] = P(bp, None, None)
        elif name == "pos":
            out[name] = P()
        else:
            out[name] = P()
    return out


def constrain(x, mesh: Mesh, *axes):
    """with_sharding_constraint helper tolerant of absent mesh axes."""
    fixed = []
    for ax in axes:
        if ax is None:
            fixed.append(None)
        elif isinstance(ax, tuple):
            sub = tuple(a for a in ax if a in mesh.axis_names)
            fixed.append(sub if sub else None)
        else:
            fixed.append(ax if ax in mesh.axis_names else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))


# ---------------------------------------------------------------------------
# Activation sharding context
# ---------------------------------------------------------------------------
#
# Without explicit activation constraints GSPMD's propagation is free to
# replicate the token batch and shard hidden dims over "data" instead —
# which it *does* for these models (full-batch activation all-reduces,
# ~TB-scale per-chip traffic).  The step builders enter this context at
# trace time; model code calls ``act()`` at block boundaries and on TP
# internals (FFN hidden, attention heads, MoE expert dim).  ``BATCH``
# resolves to the workload's data-parallel axes; when no context is
# active (unit tests, single-device examples) everything is a no-op.

import contextlib

BATCH = "__batch__"  # sentinel: the workload's DP axes tuple
SEQ = "__seq__"      # sentinel: sequence dim — "model" under sequence
#                      parallelism (halves TP traffic: AR -> RS+AG and
#                      norms/residuals run seq-sharded), else replicated

_ACT_CTX = {"mesh": None, "dp": None, "sp": False}


@contextlib.contextmanager
def activation_sharding(mesh: Optional[Mesh], dp, sp: bool = False):
    """dp: tuple of mesh axes carrying the batch dim (or None).
    sp: enable sequence parallelism over the "model" axis."""
    old = dict(_ACT_CTX)
    _ACT_CTX.update(mesh=mesh, dp=dp, sp=sp)
    try:
        yield
    finally:
        _ACT_CTX.update(old)


def act_context():
    """The active context: (mesh, dp axes, sequence parallel)."""
    return _ACT_CTX["mesh"], _ACT_CTX["dp"], _ACT_CTX["sp"]


def act(x, *axes):
    """Constrain an activation under the ambient context.

    ``axes`` uses logical names: BATCH -> context dp axes, "model"/"data"
    -> mesh axes, None -> replicated.  No-op without an active context or
    when a named dim doesn't divide evenly (constraint would be invalid).
    """
    mesh = _ACT_CTX["mesh"]
    if mesh is None:
        return x
    dp = _ACT_CTX["dp"]
    fixed = []
    for i, ax in enumerate(axes):
        if ax is SEQ:
            ax = "model" if _ACT_CTX["sp"] else None
        ax = dp if ax is BATCH else ax
        if ax is None:
            fixed.append(None)
            continue
        sub = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                    if a in mesh.axis_names)
        size = 1
        for a in sub:
            size *= mesh.shape[a]
        if not sub or x.shape[i] % size:
            fixed.append(None)
        else:
            fixed.append(sub if len(sub) > 1 else sub[0])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*fixed)))
