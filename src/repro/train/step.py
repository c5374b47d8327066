"""Train / serve step builders: model cfg + mesh + rules -> jitted fns.

This is the piece the launcher, the dry-run, the trainer and the
examples all share.  A step builder resolves:
  * parameter shardings from the logical-axis spec tree (sharding/rules),
  * input shardings per workload,
  * the pre-generation dataflow (paper Fig. 11c): FF/BP consume the bf16
    N:M operands the optimizer wrote at the previous WU (state leaf
    ``compute``) instead of re-casting/re-masking fp32 master per step,
  * the BDWP sparse-training semantics (via core/bdwp inside the model),
  * optional cross-pod N:M gradient compression (optim/compress).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import operand as O
from repro.core.sparsity import SparsityConfig
from repro.models import attention as A
from repro.models import encdec as E
from repro.models import transformer_lm as T
from repro.optim import compress as C
from repro.optim import sgd
from repro.sharding import rules as R

AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# Pre-generation plumbing: the compute tree is the differentiation root
# ---------------------------------------------------------------------------
#
# The compute tree written at WU time mixes float operands (bf16 weights,
# pruned FF/BP copies, packed vals) with non-float companions (uint8 pack
# indices, bool decay masks).  jax.grad roots must be inexact, so the
# step splits the tree by dtype: the float leaves form the grad root, the
# rest is re-merged inside the loss closure.  The cotangent tree (merged
# back into compute structure) maps to master-shaped grads via
# sgd.pregen_grads — the dense WU gradient rides on each BP operand.


def split_compute(tree):
    flat, tdef = jax.tree_util.tree_flatten(tree)
    which = [jnp.issubdtype(x.dtype, jnp.inexact) for x in flat]
    diff = [x for x, d in zip(flat, which) if d]
    aux = [x for x, d in zip(flat, which) if not d]
    return diff, (tdef, which, aux)


def merge_compute(diff, meta):
    tdef, which, aux = meta
    it_d, it_a = iter(diff), iter(aux)
    flat = [next(it_d) if d else next(it_a) for d in which]
    return jax.tree_util.tree_unflatten(tdef, flat)


# ---------------------------------------------------------------------------
# Pod-stacked split mean: compressed cross-pod sync off the critical path
# ---------------------------------------------------------------------------
#
# With compression on, the loss must NOT take the global batch mean —
# GSPMD would all-reduce every gradient over ("pod","data") densely and
# the packed sync would be pure overhead (this was the old behavior:
# 125ms compressed vs 81ms dense).  Instead the step broadcasts the grad
# root to a pod-stacked copy (n_pods, *shape), splits the batch
# (n_pods, B/P, ...), and vmaps value_and_grad over the pod dim: each
# pod-replica's gradient contraction only crosses "data", and the pod
# hop is the bucketed packed payload in optim/compress.cross_pod_sync.


def _pod_split_batch(x, mesh, n_pods):
    if x.shape[0] % n_pods:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by n_pods={n_pods}")
    xs = x.reshape(n_pods, x.shape[0] // n_pods, *x.shape[1:])
    return jax.lax.with_sharding_constraint(
        xs, NamedSharding(mesh, P("pod", ("data",),
                                  *([None] * (x.ndim - 1)))))


def _pod_stack(x, mesh, n_pods, spec):
    xs = jnp.broadcast_to(x[None], (n_pods,) + x.shape)
    return jax.lax.with_sharding_constraint(
        xs, NamedSharding(mesh, P("pod", *spec)))


def _diff_pspecs(compute_tree, master_pspecs):
    """Flat pspec list aligned with ``split_compute``'s diff leaves."""
    c_pspecs = R.pregen_pspecs(compute_tree, master_pspecs)
    flat_c = jax.tree_util.tree_flatten(compute_tree)[0]
    flat_s = jax.tree_util.tree_flatten(
        c_pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    return [s for x, s in zip(flat_c, flat_s)
            if jnp.issubdtype(x.dtype, jnp.inexact)]


# ---------------------------------------------------------------------------
# LM-family
# ---------------------------------------------------------------------------


def lm_train_step(state, batch, *, cfg, sp_cfg, opt_cfg, mesh, names,
                  compress=False, grad_pspecs=None, seq_parallel=False,
                  pregen=True, pregen_pack=False, use_pallas=False,
                  nm_backend="auto", grad_sync=None):
    def run_model(compute, b):
        hidden, _, aux = T.forward(compute, b["tokens"], cfg, sp_cfg,
                                   prefix_embeds=b.get("prefix_embeds"))
        labels = b["labels"]
        if "prefix_embeds" in b:
            hidden = hidden[:, b["prefix_embeds"].shape[1]:]
        loss = T.lm_loss(compute, hidden, labels, cfg)
        return loss + AUX_COEF * aux, (loss, aux)

    compress_on = compress and "pod" in mesh.axis_names
    dp = ("data",) if compress_on else R.batch_axes(mesh)
    with R.activation_sharding(mesh, dp, sp=seq_parallel), \
            O.backend_scope(nm_backend), A.count_paths("lm_train_step"):
        if pregen:
            # FF/BP load the operands written at the previous WU — no
            # per-step master cast, no in-model mask derivation; packed
            # (vals, idx) FF operands stream through kernels/nm_spmm on
            # the pallas backend (nm_backend)
            diff, meta = split_compute(state["compute"])
            loss_fn = lambda d, b: run_model(merge_compute(d, meta), b)
            root = diff
            root_specs = _diff_pspecs(state["compute"], grad_pspecs) \
                if compress_on else None
        else:  # legacy dataflow: cast master, re-derive masks in FF/BP
            loss_fn = lambda mt, b: run_model(jax.tree.map(
                lambda w: w.astype(jnp.bfloat16), mt), b)
            root = state["master"]
            root_specs = grad_pspecs if compress_on else None
        if compress_on:
            n_pods = mesh.shape["pod"]
            sbatch = jax.tree.map(
                lambda x: _pod_split_batch(x, mesh, n_pods), batch)
            sroot = jax.tree.map(
                lambda x, s: _pod_stack(x, mesh, n_pods, s),
                root, root_specs)
            (total, (loss, aux)), groot = jax.vmap(
                jax.value_and_grad(loss_fn, has_aux=True))(sroot, sbatch)
            total, loss, aux = total.mean(), loss.mean(), aux.mean()
        else:
            (total, (loss, aux)), groot = jax.value_and_grad(
                loss_fn, has_aux=True)(root, batch)
        grads = sgd.pregen_grads(merge_compute(groot, meta)) if pregen \
            else groot
    if compress_on:
        gc_cfg = grad_sync or C.GradCompressConfig.from_sparsity(sp_cfg)
        key = jax.random.fold_in(jax.random.PRNGKey(0x5EED),
                                 state["step"])
        grads, new_err = C.cross_pod_sync(grads, state["err"], mesh,
                                          grad_pspecs, gc_cfg, key)
        state = dict(state, err=new_err)
    new_state, compute = sgd.update(
        state_core(state), grads, opt_cfg, sp_cfg, param_names=names,
        prev_compute=state.get("compute") if pregen else None,
        pregen=pregen, pack=pregen_pack, use_pallas=use_pallas)
    new_state = dict(state, **new_state)
    if pregen:
        new_state["compute"] = compute
    metrics = {"loss": loss, "aux": aux, "total": total,
               "lr": sgd.lr_schedule(opt_cfg, state["step"])}
    return new_state, metrics


def state_core(state):
    return {k: state[k] for k in ("master", "momentum", "step")}


def init_train_state(key, cfg, family="lm", compress=False, sp_cfg=None,
                     pregen=True, pregen_pack=False, mesh=None):
    """Real (allocating) state init for the trainer/examples.

    pregen=True bootstraps the pre-generated compute tree from master
    with ``sp_cfg``'s masks — pass the SAME sp_cfg the step builder got,
    or the state structure won't match the bundle's shardings.

    compress=True allocates the flat (n_pods, T_loc*S) error-feedback
    residual slab (optim/compress) — pass the mesh so n_pods and the
    per-device slab layout resolve (the width depends on the resolved
    master shardings); without one (or without a "pod" axis) a
    single-row slab is created.
    """
    if family == "encdec":
        params, specs = E.init(key, cfg)
    else:
        params, specs = T.init(key, cfg)
    state = sgd.init_state(params)
    if compress:
        n_pods = mesh.shape.get("pod", 1) if mesh is not None else 1
        m = sp_cfg.m if sp_cfg is not None else 8
        p_pspecs = None
        if mesh is not None:
            # the same N:M-aware resolution build_lm_train does: the EF
            # width is a function of the per-device leaf blocks
            p_pspecs = R.nm_params_pspecs(specs, R.TRAIN_RULES,
                                          state["master"], mesh, sp_cfg)
        state["err"] = jnp.zeros(
            (n_pods, C.err_state_elems(state["master"], m, mesh, p_pspecs)),
            jnp.float32)
    if pregen:
        state["compute"] = sgd.pregen_tree(state["master"], sp_cfg,
                                           pack=pregen_pack)
    return state


def encdec_train_step(state, batch, *, cfg, sp_cfg, opt_cfg, mesh, names,
                      pregen=True, pregen_pack=False, use_pallas=False,
                      nm_backend="auto"):
    def run_model(compute):
        enc = E.encode(compute, batch["frames"], cfg, sp_cfg)
        hidden, _ = E.decode(compute, batch["tokens"], enc, cfg, sp_cfg)
        logits = E.logits_from_hidden(compute, hidden, cfg)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, batch["labels"][..., None],
                                   axis=-1)[..., 0]
        loss = (logz - gold).mean()
        return loss, loss

    with R.activation_sharding(mesh, R.batch_axes(mesh)), \
            O.backend_scope(nm_backend):
        if pregen:
            diff, meta = split_compute(state["compute"])
            (_, loss), gdiff = jax.value_and_grad(
                lambda d: run_model(merge_compute(d, meta)),
                has_aux=True)(diff)
            grads = sgd.pregen_grads(merge_compute(gdiff, meta))
        else:
            (_, loss), grads = jax.value_and_grad(
                lambda m: run_model(jax.tree.map(
                    lambda w: w.astype(jnp.bfloat16), m)),
                has_aux=True)(state["master"])
    new_state, compute = sgd.update(
        state_core(state), grads, opt_cfg, sp_cfg, param_names=names,
        prev_compute=state.get("compute") if pregen else None,
        pregen=pregen, pack=pregen_pack, use_pallas=use_pallas)
    new_state = dict(state, **new_state)
    if pregen:
        new_state["compute"] = compute
    return new_state, {"loss": loss, "lr": sgd.lr_schedule(opt_cfg, state["step"])}


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------


def _serve_dp(mesh, long_context):
    """Batch axes for serving activations (None: 500k batch=1 decode)."""
    return None if (mesh is None or long_context) else R.batch_axes(mesh)


def lm_prefill_step(params, batch, *, cfg, sp_cfg, mesh=None,
                    long_context=False, last_index=None):
    """Prefill: build the KV cache and return next-token logits.

    last_index: optional (B,) int array of per-request *last real token*
    indices.  With right-padded prompts (the serve engine pads every
    prompt to one static bucket so prefill compiles once), logits must be
    read at each request's own final position, not at s-1.
    """
    b, s = batch["tokens"].shape
    prefix = batch.get("prefix_embeds")
    s_tot = s + (prefix.shape[1] if prefix is not None else 0)
    with R.activation_sharding(mesh, _serve_dp(mesh, long_context)), \
            A.count_paths("lm_prefill_step"):
        cache = T.init_lm_cache(cfg, b, s_tot)
        hidden, cache, _ = T.forward(params, batch["tokens"], cfg, sp_cfg,
                                     prefix_embeds=prefix, cache=cache)
        if last_index is None:
            h_last = hidden[:, -1:]
        else:
            idx = jnp.asarray(last_index, jnp.int32).reshape(b, 1, 1)
            h_last = jnp.take_along_axis(
                hidden, jnp.broadcast_to(idx, (b, 1, hidden.shape[-1])),
                axis=1)
        logits = T.logits_from_hidden(params, h_last, cfg)
    return logits, cache


def lm_decode_step(params, cache, token, pos, *, cfg, sp_cfg, mesh=None,
                   long_context=False, per_slot=False):
    """One decode step.

    pos: scalar — the classic synchronized batch (all rows at the same
    depth, shared cache cursor); or (B,) vector with per_slot=True — the
    continuous-batching mode where every row is an independent request
    slot at its own position (cache writes/masks are slot-indexed).
    """
    b = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    else:
        positions = pos.reshape(b, 1)
    with R.activation_sharding(mesh, _serve_dp(mesh, long_context)):
        hidden, new_cache, _ = T.forward(params, token, cfg, sp_cfg,
                                         cache=cache, decode=True,
                                         positions=positions,
                                         per_slot=per_slot)
        logits = T.logits_from_hidden(params, hidden, cfg)
    return logits, new_cache


def encdec_prefill_step(params, batch, *, cfg, sp_cfg, mesh=None):
    with R.activation_sharding(mesh, _serve_dp(mesh, False)):
        enc = E.encode(params, batch["frames"], cfg, sp_cfg)
        b, s = batch["tokens"].shape
        cache = E.init_cache(cfg, b, s)
        hidden, cache = E.decode(params, batch["tokens"], enc, cfg, sp_cfg,
                                 cache=cache)
        logits = E.logits_from_hidden(params, hidden[:, -1:], cfg)
    return logits, cache, enc


def encdec_decode_step(params, cache, enc_out, token, pos, *, cfg, sp_cfg,
                       mesh=None):
    b = token.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    with R.activation_sharding(mesh, _serve_dp(mesh, False)):
        hidden, new_cache = E.decode(params, token, enc_out, cfg, sp_cfg,
                                     cache=cache, decode_step=True,
                                     positions=positions)
        logits = E.logits_from_hidden(params, hidden, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Builders: resolve shardings + jit
# ---------------------------------------------------------------------------


def _named(fn: partial) -> partial:
    """``jax.jit`` names its program (``jit_<name>`` in a profiler trace)
    after ``__name__``, which a ``functools.partial`` lacks
    (``jit__unknown``): give it its function's."""
    fn.__name__ = fn.func.__name__
    return fn


@dataclasses.dataclass
class StepBundle:
    step_fn: callable            # jitted
    state_shardings: object
    input_pspecs: dict
    names: list
    specs: object                # logical-axis tree
    mesh: Optional[Mesh] = None  # mesh the bundle was resolved against


def abstract_compute_tree(aparams, sp_cfg, pack=False):
    """ShapeDtypeStruct compute tree (zero allocation) for builders/dry-run."""
    return jax.eval_shape(
        partial(sgd.pregen_tree, sp_cfg=sp_cfg, pack=pack), aparams)


def _train_state_pspecs(p_pspecs, aparams, mesh, sp_cfg, *, compress,
                        pregen, pregen_pack):
    """State pspecs incl. the pre-generated compute tree; asserts that no
    resolved sharding splits an N:M group or a packed run."""
    state_pspecs = {"master": p_pspecs, "momentum": p_pspecs, "step": P()}
    if compress and "pod" in mesh.axis_names:
        state_pspecs["err"] = R.grad_sync_pspecs(mesh)["err"]
    if pregen:
        acompute = abstract_compute_tree(aparams, sp_cfg, pack=pregen_pack)
        c_pspecs = R.pregen_pspecs(acompute, p_pspecs)
        R.assert_nm_unsplit(c_pspecs, acompute, mesh, sp_cfg)
        state_pspecs["compute"] = c_pspecs
    return state_pspecs


def build_lm_train(cfg, mesh: Mesh, sp_cfg: SparsityConfig,
                   opt_cfg: sgd.SGDConfig, *, compress=False,
                   donate=True, seq_parallel=False, pregen=True,
                   pregen_pack=False, use_pallas=False,
                   nm_backend="auto", grad_sync=None) -> StepBundle:
    aparams, specs = T.init(jax.random.PRNGKey(0), cfg, abstract=True)
    rules = R.TRAIN_RULES
    # N:M-aware resolution: a mesh axis that would split an M-group
    # along a grouped weight axis is dropped, and the result is asserted
    p_pspecs = R.nm_params_pspecs(specs, rules, aparams, mesh, sp_cfg)
    R.assert_nm_unsplit(p_pspecs, aparams, mesh, sp_cfg)
    names = sgd._names_of(p_pspecs)
    state_pspecs = _train_state_pspecs(p_pspecs, aparams, mesh, sp_cfg,
                                       compress=compress, pregen=pregen,
                                       pregen_pack=pregen_pack)
    state_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), state_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    dp = R.batch_axes(mesh)
    in_pspecs = {"tokens": P(dp, None), "labels": P(dp, None)}
    if cfg.name.startswith("internvl"):
        in_pspecs["prefix_embeds"] = P(dp, None, None)
    batch_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), in_pspecs,
                            is_leaf=lambda x: isinstance(x, P))

    fn = _named(partial(lm_train_step, cfg=cfg, sp_cfg=sp_cfg,
                        opt_cfg=opt_cfg, mesh=mesh, names=names,
                        compress=compress, grad_pspecs=p_pspecs,
                        seq_parallel=seq_parallel, pregen=pregen,
                        pregen_pack=pregen_pack, use_pallas=use_pallas,
                        nm_backend=nm_backend, grad_sync=grad_sync))
    jitted = jax.jit(fn,
                     in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,) if donate else ())
    return StepBundle(jitted, state_sh, in_pspecs, names, specs, mesh)


def build_encdec_train(cfg, mesh: Mesh, sp_cfg, opt_cfg,
                       donate=True, pregen=True, pregen_pack=False,
                       use_pallas=False, nm_backend="auto") -> StepBundle:
    aparams, specs = E.init(jax.random.PRNGKey(0), cfg, abstract=True)
    p_pspecs = R.nm_params_pspecs(specs, R.TRAIN_RULES, aparams, mesh,
                                  sp_cfg)
    R.assert_nm_unsplit(p_pspecs, aparams, mesh, sp_cfg)
    names = sgd._names_of(p_pspecs)
    state_pspecs = _train_state_pspecs(p_pspecs, aparams, mesh, sp_cfg,
                                       compress=False, pregen=pregen,
                                       pregen_pack=pregen_pack)
    state_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), state_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    dp = R.batch_axes(mesh)
    in_pspecs = {"frames": P(dp, None, None), "tokens": P(dp, None),
                 "labels": P(dp, None)}
    batch_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), in_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    fn = _named(partial(encdec_train_step, cfg=cfg, sp_cfg=sp_cfg,
                        opt_cfg=opt_cfg, mesh=mesh, names=names,
                        pregen=pregen, pregen_pack=pregen_pack,
                        use_pallas=use_pallas, nm_backend=nm_backend))
    jitted = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                     out_shardings=(state_sh, None),
                     donate_argnums=(0,) if donate else ())
    return StepBundle(jitted, state_sh, in_pspecs, names, specs, mesh)


def restore_with_pregen(mgr, like_state, step=None, shardings=None, *,
                        sp_cfg=None, pregen_pack=False):
    """Checkpoint restore that upgrades older-dataflow checkpoints.

    Two generations of checkpoint mismatch the current state tree:
      * pre-pregen — no ``compute`` leaf at all;
      * dict-sites-only pregen — a ``compute`` tree whose ``{"w": ...}``
        sites are operand dicts but whose bare-array MoE expert leaves
        are still plain bf16 copies (``pregen_tree(bare_sites=False)``
        reproduces that structure).
    Either way the legacy subtree (master/momentum/step[/err]) restores
    and the compute tree regenerates from the restored master — the
    pre-generated operands are a pure function of master, so both
    upgrades are exact.
    """
    try:
        return mgr.restore(like_state, step=step, shardings=shardings)
    except ValueError as full_err:
        legacy_like = {k: v for k, v in like_state.items() if k != "compute"}
        legacy_sh = None if shardings is None else \
            {k: v for k, v in shardings.items() if k != "compute"}
        attempts = [(legacy_like, legacy_sh)]
        if "compute" in like_state:
            old_compute = jax.eval_shape(
                partial(sgd.pregen_tree, sp_cfg=sp_cfg, pack=pregen_pack,
                        bare_sites=False), legacy_like["master"])
            if (jax.tree_util.tree_structure(old_compute)
                    != jax.tree_util.tree_structure(like_state["compute"])):
                old_sh = None if shardings is None else dict(
                    legacy_sh, compute=_old_compute_shardings(
                        old_compute, shardings["compute"],
                        shardings["master"]))
                attempts.append((dict(legacy_like, compute=old_compute),
                                 old_sh))
        restored = None
        for like, sh in attempts:
            try:
                restored = mgr.restore(like, step=step, shardings=sh)
                break
            except ValueError:
                continue
        if restored is None:
            # no upgrade structure matches either (arch / compress /
            # pack-mode mismatch): surface the original full-structure
            # error, not a misleading legacy-subtree one
            raise full_err from None
        out = {k: v for k, v in restored.items() if k != "compute"}
        out["compute"] = sgd.pregen_tree(out["master"], sp_cfg,
                                         pack=pregen_pack)
        if shardings is not None:
            out = {k: jax.device_put(out[k], shardings[k]) for k in out}
        return out


def _old_compute_shardings(old_compute, new_compute_sh, master_sh):
    """Shardings for a dict-sites-only (pre-MoE) compute structure, so
    the upgrade restore never stages leaves on one device: dict sites
    (PregenOp there and now) match the current compute shardings
    leaf-for-leaf; bare expert leaves (plain bf16 copies there, PregenOp
    operands now) shard like their master weight (same shape)."""
    def walk(old_node, new_sh, m_sh):
        if isinstance(old_node, O.SparseOperand):
            return new_sh  # dict sites kept their operand structure
        if isinstance(old_node, dict):
            return {k: walk(old_node[k],
                            new_sh[k] if isinstance(new_sh, dict) else new_sh,
                            m_sh[k] if isinstance(m_sh, dict) else m_sh)
                    for k in old_node}
        # array leaf: a matching leaf sharding, else the master weight's
        return new_sh \
            if not isinstance(new_sh, (dict, O.SparseOperand)) else m_sh

    return walk(old_compute, new_compute_sh, master_sh)


def build_lm_serve(cfg, mesh: Mesh, sp_cfg: SparsityConfig, input_specs,
                   *, long_context=False, prefill=False,
                   packed=False) -> StepBundle:
    """packed=True: serve from shared-mode pre-gathered N:M weights —
    reduced-K matmuls (M/N x fewer FLOPs AND weight bytes).  The param
    tree (and its shardings) is transformed by bdwp.pack_tree_shared;
    callers pack real weights with the same function."""
    from repro.core import bdwp as B

    aparams, specs = T.init(jax.random.PRNGKey(0), cfg, abstract=True)
    rules = R.SERVE_LONG_RULES if long_context else R.SERVE_BATCH_RULES
    p_pspecs = R.nm_params_pspecs(specs, rules, aparams, mesh, sp_cfg)
    check_tree = aparams
    if packed:
        check_tree, p_pspecs = B.pack_tree_shared(aparams, sp_cfg,
                                                  pspecs=p_pspecs)
    R.assert_nm_unsplit(p_pspecs, check_tree, mesh, sp_cfg)
    param_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), p_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    in_pspecs = R.serve_input_pspecs(input_specs, mesh,
                                     long_context=long_context)
    in_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), in_pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    if prefill:
        fn = _named(partial(lm_prefill_step, cfg=cfg, sp_cfg=sp_cfg,
                            mesh=mesh, long_context=long_context))
        jitted = jax.jit(fn, in_shardings=(param_sh, in_sh))
    else:
        fn = _named(partial(lm_decode_step, cfg=cfg, sp_cfg=sp_cfg,
                            mesh=mesh, long_context=long_context))
        jitted = jax.jit(
            fn,
            in_shardings=(param_sh, in_sh["cache"], in_sh["token"],
                          in_sh["pos"]),
            out_shardings=(None, in_sh["cache"]),
            donate_argnums=(1,),
        )
    return StepBundle(jitted, param_sh, in_pspecs, [], specs, mesh)


def build_encdec_serve(cfg, mesh: Mesh, sp_cfg, input_specs, *,
                       prefill=False) -> StepBundle:
    aparams, specs = E.init(jax.random.PRNGKey(0), cfg, abstract=True)
    p_pspecs = R.params_pspecs(specs, R.SERVE_BATCH_RULES, aparams, mesh)
    param_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), p_pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    in_pspecs = R.serve_input_pspecs(input_specs, mesh, long_context=False)
    in_sh = jax.tree.map(lambda ps: NamedSharding(mesh, ps), in_pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    if prefill:
        fn = _named(partial(encdec_prefill_step, cfg=cfg, sp_cfg=sp_cfg,
                            mesh=mesh))
        jitted = jax.jit(fn, in_shardings=(param_sh, in_sh))
    else:
        fn = _named(partial(encdec_decode_step, cfg=cfg, sp_cfg=sp_cfg,
                            mesh=mesh))
        jitted = jax.jit(
            fn,
            in_shardings=(param_sh, in_sh["cache"], in_sh["enc_out"],
                          in_sh["token"], in_sh["pos"]),
            out_shardings=(None, in_sh["cache"]),
            donate_argnums=(1,),
        )
    return StepBundle(jitted, param_sh, in_pspecs, [], specs, mesh)
