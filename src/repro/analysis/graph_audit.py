"""nmlint graph rules (NM2xx/NM3xx/NM4xx): jaxpr/HLO invariants of the
compiled programs, audited over a representative config matrix.

The matrix (one case per workload family the repo trains/serves):

  dense_lm        qwen3-8b smoke, 2:8 bdwp, pregen_pack=True — packed
                  train forward on both backends + recompile detector
  moe             granite-moe-1b smoke, 2:4 bdwp — bare-array expert
                  stacks, N:M-shape-filtered mask census
  conv            ResNet9, 2:8 bdwp pregen — conv mask derivation +
                  selection-free forward
  serve_u4        qwen3-8b smoke ServeEngine, element-packed u4 store —
                  compiled decode HLO entry params + scatter census +
                  donation aliasing + dispatch-cache stability
  kernels         the packed-math kernel surfaces (nm_spmm, fused
                  update, grad compress/decompress) on both backends —
                  accumulation-dtype audit (numerics family only)
  gradsync_mesh8  qwen3-8b smoke on the (pod, data, model) 8-device
                  mesh with N:M-compressed cross-pod sync (mesh8 only)

Rules are grouped into *families* — ``graph`` (NM2xx structure),
``numerics`` (NM3xx dtype provenance, repro/analysis/dtype_flow), and
``buffers`` (NM401/NM403 donation + dispatch, repro/analysis/
buffer_audit).  Each case traces its program ONCE (``trace_once``) and
compiles at most ONE executable, then shares those artifacts across
every family's checks, so wall-clock does not scale with rule count.
A case asked for no family it covers returns ``None`` and is skipped.

Every census helper here is THE implementation — benchmarks
(pregen_bench) and tests call these instead of keeping private copies,
so an invariant has exactly one definition.  HLO structure comes from
``launch/hlo_cost.parse_module``/``entry_param_shapes`` — extended,
not duplicated.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.findings import Finding

SCATTER_PRIMS = ("scatter", "scatter-add")
CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "callback")

GRAPH = "graph"
NUMERICS = "numerics"
BUFFERS = "buffers"
ALL_FAMILIES = (GRAPH, NUMERICS, BUFFERS)


# ---------------------------------------------------------------------------
# Shared-artifact helpers
# ---------------------------------------------------------------------------


def _structs(tree):
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def trace_once(fn, *args):
    """Trace ``fn`` exactly once -> (ClosedJaxpr, output tree paths).

    The jaxpr feeds every census/provenance check for the case; the
    paths (one per flattened outvar, '/'-joined tree keys) let NM302
    name which state leaf an output is without a second trace.
    """
    import jax

    box = {}

    def wrapper(*a):
        out = fn(*a)
        box["treedef"] = jax.tree_util.tree_structure(out)
        return out

    jaxpr = jax.make_jaxpr(wrapper)(*args)
    n_out = len(jaxpr.jaxpr.outvars)
    skeleton = jax.tree_util.tree_unflatten(box["treedef"],
                                            list(range(n_out)))
    paths = [""] * n_out
    for path, leaf in jax.tree_util.tree_flatten_with_path(skeleton)[0]:
        paths[leaf] = "/".join(str(getattr(k, "key", k)) for k in path)
    return jaxpr, paths


# ---------------------------------------------------------------------------
# Census helpers — single source of truth (benchmarks import these)
# ---------------------------------------------------------------------------


def _is_jaxpr(fn) -> bool:
    return hasattr(fn, "eqns") or hasattr(fn, "jaxpr")


def mask_census(fn, *args, nm=None) -> int:
    """N:M mask selections (top_k/sort) in ``fn`` — a function to trace
    or an already-traced jaxpr (nm=(n, m) filters router top_k)."""
    from repro.launch.hlo_cost import (MASK_PRIMS, count_jaxpr_prims,
                                       count_mask_ops, nm_selection_pred)
    if _is_jaxpr(fn):
        pred = nm_selection_pred(*nm) if nm is not None else None
        return count_jaxpr_prims(fn, names=MASK_PRIMS, pred=pred)
    return count_mask_ops(fn, *args, nm=nm)


def scatter_census(fn, *args) -> int:
    """Scatter primitives in the traced ``fn`` (0 == packed operands are
    consumed directly, never decompressed)."""
    import jax
    from repro.launch.hlo_cost import count_jaxpr_prims
    jaxpr = fn if _is_jaxpr(fn) else jax.make_jaxpr(fn)(*args)
    return count_jaxpr_prims(jaxpr, names=SCATTER_PRIMS)


def callback_census(fn, *args) -> int:
    """Host callbacks in the traced ``fn`` (0 == hot path never leaves
    the device)."""
    import jax
    from repro.launch.hlo_cost import count_jaxpr_prims
    jaxpr = fn if _is_jaxpr(fn) else jax.make_jaxpr(fn)(*args)
    return count_jaxpr_prims(jaxpr, names=CALLBACK_PRIMS)


def pallas_call_census(fn, *args, kernel: Optional[str] = None) -> int:
    """pallas_call invocations in the traced ``fn`` (== packed sites on
    the pallas backend).  ``kernel`` counts only the calls whose kernel
    ``name=`` fully matches that regex (e.g. ``"nm_spmm_[0-9_]+(u4)?"``
    — a train step also runs fused_update)."""
    import jax
    from repro.launch.hlo_cost import count_jaxpr_prims
    jaxpr = fn if _is_jaxpr(fn) else jax.make_jaxpr(fn)(*args)
    pred = None if kernel is None else (
        lambda eqn: re.fullmatch(kernel, str(eqn.params.get("name"))))
    return count_jaxpr_prims(jaxpr, names=("pallas_call",), pred=pred)


def prunable_sites(master, sp_cfg) -> List[str]:
    """Tree paths of every prunable parameter (``bdwp.pregen_site`` on
    the logical shape) — the denominator of the mask-once invariant."""
    import jax
    from repro.core import bdwp
    from repro.optim import sgd

    names = []
    for path, w in jax.tree_util.tree_flatten_with_path(master)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        lshape, _ = sgd._logical_shape(name, w.shape)
        if bdwp.pregen_site(name, lshape, sp_cfg):
            names.append(name)
    return names


def packed_dense_shapes(params_tree) -> set:
    """Dense-equivalent shapes of every PackedOp leaf in a tree — what a
    packed decode must NOT materialize as an entry parameter."""
    import jax
    from repro.core import operand as O

    shapes = set()
    for leaf in jax.tree.leaves(
            params_tree, is_leaf=lambda x: isinstance(x, O.PackedOp)):
        if isinstance(leaf, O.PackedOp):
            v = leaf.vals.shape
            cfg = leaf.cfg
            shapes.add(v[:-2] + (v[-2] * cfg.m // cfg.n,) + v[-1:])
    return shapes


def check_scatter_free(fn, args, case: str, label: str = "",
                       allowed: int = 0) -> Tuple[List[Finding], int]:
    """NM201 as a finding-producer: the traced fn must contain no more
    than ``allowed`` scatter primitives (``allowed`` > 0 when the same
    program carries legitimate non-weight scatters, e.g. per-slot
    KV-cache writes — pass the dense-control census).  Returns
    (findings, census)."""
    n = scatter_census(fn, *args)
    if n > allowed:
        return [Finding(
            "NM201", case, 0,
            f"{label or 'traced packed path'} contains {n} scatter "
            f"op(s) (baseline {allowed}) — (vals, idx) is being "
            f"decompressed to dense")], n
    return [], n


def check_mask_once(fn, args, expected: int, nm, case: str,
                    label: str = "") -> Tuple[List[Finding], int]:
    """NM202 as a finding-producer: the traced fn must derive exactly
    ``expected`` N:M mask selections.  Returns (findings, census)."""
    n = mask_census(fn, *args, nm=nm)
    if n != expected:
        return [Finding(
            "NM202", case, 0,
            f"{label or 'traced step'} derives {n} N:M masks, expected "
            f"{expected} (one per prunable param)")], n
    return [], n


def check_callback_free(fn, args, case: str,
                        label: str = "") -> Tuple[List[Finding], int]:
    """NM205 as a finding-producer: zero host callbacks in the traced
    fn.  Returns (findings, census)."""
    n = callback_census(fn, *args)
    if n:
        return [Finding(
            "NM205", case, 0,
            f"{label or 'traced step'} traces {n} host callback(s) — "
            f"the hot path leaves the device")], n
    return [], n


def check_no_dense_entry_params(hlo_text: str, dense_shapes: set,
                                case: str) -> List[Finding]:
    """NM203: the compiled program's ENTRY parameters must not carry a
    weight-dtype array shaped like a packed site's dense equivalent."""
    from repro.launch.hlo_cost import entry_param_shapes

    weight_dtypes = {"bf16", "f16", "f32"}
    findings = []
    for pname, dtype, shape in entry_param_shapes(hlo_text):
        if dtype in weight_dtypes and tuple(shape) in dense_shapes:
            findings.append(Finding(
                "NM203", case, 0,
                f"entry parameter {pname} is a dense {dtype}{list(shape)}"
                f" weight matching a packed site's dense equivalent — "
                f"the store pre-decompressed outside the step"))
    return findings


def check_group_integrity(pspecs_tree, params_tree, mesh, sp_cfg,
                          case: str) -> List[Finding]:
    """NM204 as a finding-producer around rules.assert_nm_unsplit."""
    from repro.sharding import rules as R
    try:
        R.assert_nm_unsplit(pspecs_tree, params_tree, mesh, sp_cfg)
    except AssertionError as e:
        return [Finding("NM204", case, 0, str(e))]
    return []


def check_recompile_stable(jitted, case: str, runs: int = 2,
                           run_fn=None) -> Tuple[List[Finding], int]:
    """NM206: after ``runs`` same-shaped invocations (performed by
    ``run_fn``), the jit cache must hold exactly one entry.  Returns
    (findings, cache_size); cache_size -1 when the jax build exposes no
    ``_cache_size`` (check skipped, never failed)."""
    if not hasattr(jitted, "_cache_size"):
        return [], -1
    if run_fn is not None:
        run_fn()
    size = int(jitted._cache_size())
    if size > 1:
        return [Finding(
            "NM206", case, 0,
            f"compiled step cache holds {size} entries after {runs} "
            f"same-shaped steps — something in the step signature "
            f"(weak types, python scalars, donation) retriggers "
            f"compilation")], size
    return [], size


def _numerics_step_checks(step_jaxpr, step_args, out_paths, nm, case: str,
                          label: str, check_302: bool = True
                          ) -> Tuple[List[Finding], dict]:
    """NM301 (+ optionally NM302) over one already-traced train step —
    the shared numerics pass every train case runs on its cached
    jaxpr."""
    from repro.analysis import dtype_flow as DF

    in_tags = DF.tag_inputs(*step_args)
    findings, selections = DF.check_master_mask_source(
        step_jaxpr, in_tags, nm, case, label)
    stats = {"selections_inspected": selections,
             "double_round_checked": bool(check_302)}
    if check_302:
        findings.extend(DF.check_no_double_round(
            step_jaxpr, in_tags, out_paths, case, label))
    return findings, stats


# ---------------------------------------------------------------------------
# Config-matrix cases
# ---------------------------------------------------------------------------


def _lm_batch(batch, seq):
    import jax.numpy as jnp
    return {"tokens": jnp.zeros((batch, seq), jnp.int32),
            "labels": jnp.zeros((batch, seq), jnp.int32)}


def audit_dense_lm(families: Sequence[str] = (GRAPH,)
                   ) -> Optional[Tuple[dict, List[Finding]]]:
    """Dense-architecture LM (qwen3 smoke), 2:8 bdwp, packed pregen:
    mask-once, scatter-free packed forward (both backends), no host
    callbacks, stable compile cache over real steps; numerics: the
    selections score the fp32 master and no state output double-rounds.
    One step trace serves every family."""
    fam = set(families)
    if not fam & {GRAPH, NUMERICS}:
        return None
    import jax
    from repro.configs import get_arch
    from repro.core import operand as O
    from repro.core.sparsity import SparsityConfig
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer_lm as T
    from repro.optim import sgd
    from repro.train import step as ST

    cfg = get_arch("qwen3-8b").smoke
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    opt = sgd.SGDConfig(lr=0.05, total_steps=100)
    mesh = make_host_mesh()
    # batch divides the data axis even when --mesh8 forced 8 devices
    batch, seq = max(2, int(dict(mesh.shape).get("data", 1))), 32

    state = ST.init_train_state(jax.random.PRNGKey(0), cfg, sp_cfg=sp,
                                pregen_pack=True)
    sites = prunable_sites(state["master"], sp)
    b0 = _lm_batch(batch, seq)
    bundle = ST.build_lm_train(cfg, mesh, sp, opt, donate=False,
                               pregen_pack=True)

    findings: List[Finding] = []
    step_args = (_structs(state), _structs(b0))
    step_jaxpr, out_paths = trace_once(bundle.step_fn, *step_args)
    metrics = {"arch": "qwen3-8b-smoke", "nm": f"{sp.n}:{sp.m}",
               "prunable_params": len(sites)}

    if GRAPH in fam:
        fs, masks = check_mask_once(step_jaxpr, (), len(sites),
                                    (sp.n, sp.m), "dense_lm",
                                    "pregen train step")
        findings.extend(fs)

        def forward_loss(backend):
            def fn(compute, b):
                with O.backend_scope(backend):
                    hidden, _, aux = T.forward(compute, b["tokens"], cfg,
                                               sp)
                    return T.lm_loss(compute, hidden, b["labels"], cfg) \
                        + 0.01 * aux
            return fn

        scatters = {}
        for backend in ("jnp", "pallas"):
            fwd_args = (_structs(state["compute"]), _structs(b0))
            fs, scatters[backend] = check_scatter_free(
                forward_loss(backend), fwd_args, "dense_lm",
                f"{backend}-backend packed train forward")
            findings.extend(fs)

        fs, callbacks = check_callback_free(step_jaxpr, (), "dense_lm",
                                            "train step")
        findings.extend(fs)

        # recompile detector: two REAL same-shaped steps, one cache entry
        state = jax.device_put(state, bundle.state_shardings)

        def run_two():
            nonlocal state
            for _ in range(2):
                state, metrics_ = bundle.step_fn(state, b0)
            jax.block_until_ready(metrics_["loss"])

        rc_findings, cache_size = check_recompile_stable(
            bundle.step_fn, "dense_lm", run_fn=run_two)
        findings.extend(rc_findings)
        metrics.update(mask_ops=masks, forward_scatter_ops=scatters,
                       host_callbacks=callbacks,
                       compile_cache_entries=cache_size)

    if NUMERICS in fam:
        fs, stats = _numerics_step_checks(
            step_jaxpr, step_args, out_paths, (sp.n, sp.m), "dense_lm",
            "pregen train step")
        findings.extend(fs)
        metrics["numerics"] = stats

    return metrics, findings


def audit_moe(families: Sequence[str] = (GRAPH,)
              ) -> Optional[Tuple[dict, List[Finding]]]:
    """MoE LM (granite smoke), 2:4 bdwp: mask-once over bare-array
    expert stacks with the N:M-shape-filtered census (the 8-expert
    router top_k must not be miscounted), no host callbacks; numerics:
    master-scored selections (router top_k exempt via the nm-shape
    filter) and no double-rounded state."""
    fam = set(families)
    if not fam & {GRAPH, NUMERICS}:
        return None
    import jax
    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.launch.mesh import make_host_mesh
    from repro.optim import sgd
    from repro.train import step as ST

    cfg = get_arch("granite-moe-1b-a400m").smoke
    sp = SparsityConfig(n=2, m=4, method="bdwp")
    opt = sgd.SGDConfig(lr=0.05, total_steps=100)
    mesh = make_host_mesh()

    state = ST.init_train_state(jax.random.PRNGKey(0), cfg, sp_cfg=sp)
    sites = prunable_sites(state["master"], sp)
    b0 = _lm_batch(max(2, int(dict(mesh.shape).get("data", 1))), 32)
    bundle = ST.build_lm_train(cfg, mesh, sp, opt, donate=False,
                               pregen=True)

    findings: List[Finding] = []
    step_args = (_structs(state), _structs(b0))
    step_jaxpr, out_paths = trace_once(bundle.step_fn, *step_args)
    metrics = {"arch": "granite-moe-1b-smoke", "nm": f"{sp.n}:{sp.m}",
               "prunable_params": len(sites)}

    if GRAPH in fam:
        fs, masks = check_mask_once(step_jaxpr, (), len(sites),
                                    (sp.n, sp.m), "moe", "MoE pregen step")
        findings.extend(fs)
        fs, callbacks = check_callback_free(step_jaxpr, (), "moe",
                                            "MoE train step")
        findings.extend(fs)
        metrics.update(mask_ops=masks, host_callbacks=callbacks)

    if NUMERICS in fam:
        fs, stats = _numerics_step_checks(
            step_jaxpr, step_args, out_paths, (sp.n, sp.m), "moe",
            "MoE pregen step")
        findings.extend(fs)
        metrics["numerics"] = stats

    return metrics, findings


def audit_conv(families: Sequence[str] = (GRAPH,)
               ) -> Optional[Tuple[dict, List[Finding]]]:
    """Convnet (ResNet9), 2:8 bdwp pregen: the mask derivation pays one
    selection per prunable conv param, and the forward over the
    pre-generated tree re-derives none; numerics: the derivation scores
    the fp32 master (the PR 3 conv-mask incident surface)."""
    fam = set(families)
    if not fam & {GRAPH, NUMERICS}:
        return None
    import jax
    import jax.numpy as jnp
    from repro.core.sparsity import SparsityConfig
    from repro.models import convnets as C
    from repro.optim import sgd

    sp = SparsityConfig(n=2, m=8, method="bdwp")
    params = C.resnet9_init(jax.random.PRNGKey(0), num_classes=10,
                            width=32)
    sites = prunable_sites(params, sp)

    findings: List[Finding] = []
    derive = partial(sgd.pregen_tree, sp_cfg=sp)
    derive_args = (_structs(params),)
    derive_jaxpr, _ = trace_once(derive, *derive_args)

    compute = sgd.pregen_tree(params, sp)
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.bfloat16)

    def fwd(tree, xx):
        return C.resnet9_apply(tree, xx, sp)

    fwd_args = (_structs(compute), x)
    fwd_jaxpr, _ = trace_once(fwd, *fwd_args)
    metrics = {"arch": "resnet9", "nm": f"{sp.n}:{sp.m}",
               "prunable_params": len(sites)}

    if GRAPH in fam:
        fs, masks = check_mask_once(derive_jaxpr, (), len(sites),
                                    (sp.n, sp.m), "conv",
                                    "conv pregen derivation")
        findings.extend(fs)
        fs, fwd_masks = check_mask_once(
            fwd_jaxpr, (), 0, (sp.n, sp.m), "conv",
            "conv forward over the pre-generated tree")
        findings.extend(fs)
        fs, callbacks = check_callback_free(fwd_jaxpr, (), "conv",
                                            "conv forward")
        findings.extend(fs)
        metrics.update(mask_ops=masks, forward_mask_ops=fwd_masks,
                       host_callbacks=callbacks)

    if NUMERICS in fam:
        from repro.analysis import dtype_flow as DF
        fs, selections = DF.check_master_mask_source(
            derive_jaxpr, DF.tag_inputs(*derive_args), (sp.n, sp.m),
            "conv", "conv pregen derivation")
        findings.extend(fs)
        metrics["numerics"] = {"selections_inspected": selections,
                               "double_round_checked": False}

    return metrics, findings


def audit_serve_u4(families: Sequence[str] = (GRAPH,)
                   ) -> Optional[Tuple[dict, List[Finding]]]:
    """Element-packed u4 serve decode (qwen3 smoke ServeEngine): zero
    scatters in the decode jaxpr beyond the dense control, no
    dense-shaped packed weight among the compiled step's ENTRY
    parameters, no host callbacks; buffers: the donated KV cache really
    aliases (NM401) and the prefill/seat/decode jits hold one cache
    entry after a real workload (NM403).  One decode trace + one
    compile serve every family."""
    fam = set(families)
    if not fam & {GRAPH, NUMERICS, BUFFERS}:
        return None
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.models import transformer_lm as T
    from repro.serve import ServeConfig, ServeEngine

    cfg = get_arch("qwen3-8b").smoke
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    params, _ = T.init(jax.random.PRNGKey(0), cfg)
    params = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    geom = dict(n_slots=2, prompt_bucket=8, max_len=16)
    engine = ServeEngine(params, cfg, sp, ServeConfig(packed=True, **geom))

    findings: List[Finding] = []
    b = engine.batcher
    args = (b.params, b.kv.cache, b.tokens, b.positions)
    decode_jaxpr, _ = trace_once(b._decode, *args)
    metrics = {"arch": "qwen3-8b-smoke", "nm": f"{sp.n}:{sp.m}",
               "idx_bits": engine.store.idx_bits,
               "packed_sites": engine.store.n_packed}

    hlo = None
    if fam & {GRAPH, BUFFERS}:
        hlo = b._decode.lower(*args).compile().as_text()

    if GRAPH in fam:
        # dense-store control on the same geometry: the per-slot KV-cache
        # writes scatter legitimately, so "scatter-free packed path" means
        # packing adds ZERO scatters over the dense decode, not zero total
        dense = ServeEngine(params, cfg, sp,
                            ServeConfig(packed=False, **geom))
        db = dense.batcher
        dense_scatters = scatter_census(
            db._decode, db.params, db.kv.cache, db.tokens, db.positions)
        fs, scatters = check_scatter_free(
            decode_jaxpr, (), "serve_u4", "packed u4 decode step",
            allowed=dense_scatters)
        findings.extend(fs)
        fs, callbacks = check_callback_free(decode_jaxpr, (), "serve_u4",
                                            "decode step")
        findings.extend(fs)
        dense_shapes = packed_dense_shapes(engine.store.params)
        findings.extend(check_no_dense_entry_params(hlo, dense_shapes,
                                                    "serve_u4"))
        metrics.update(decode_scatter_ops=scatters,
                       decode_scatter_ops_dense_control=dense_scatters,
                       host_callbacks=callbacks,
                       dense_equiv_shapes_checked=len(dense_shapes))

    if NUMERICS in fam:
        # no fp32 master exists at serve time, so NM301 runs as a
        # structural negative: the pass must find nothing to flag
        from repro.analysis import dtype_flow as DF
        fs, selections = DF.check_master_mask_source(
            decode_jaxpr, DF.tag_inputs(*args), (sp.n, sp.m), "serve_u4",
            "packed u4 decode step")
        findings.extend(fs)
        metrics["numerics"] = {"selections_inspected": selections,
                               "double_round_checked": False}

    if BUFFERS in fam:
        from repro.analysis import buffer_audit as BA
        # the solo decode donates the KV cache (argnums=(1,)) — it must
        # really alias or decode HBM silently doubles
        fs, donation = BA.check_donation_aliased(
            hlo, b.kv.cache, "serve_u4", "packed u4 decode step")
        findings.extend(fs)

        def workload():
            engine.submit([1, 2, 3], max_new_tokens=3)
            engine.submit([4, 5, 6, 7], max_new_tokens=3)
            engine.run(max_steps=12)

        fs, cache_sizes = BA.check_dispatch_stable(
            {"prefill": b._prefill, "seat": b._seat, "decode": b._decode},
            "serve_u4", run_fn=workload)
        findings.extend(fs)
        metrics["buffers"] = dict(donation, dispatch_cache=cache_sizes)

    return metrics, findings


def audit_kernels(families: Sequence[str] = (GRAPH,)
                  ) -> Optional[Tuple[dict, List[Finding]]]:
    """The kernels case: NM303 accumulation-dtype audit over every
    packed-math kernel surface (see dtype_flow.audit_kernels)."""
    from repro.analysis import dtype_flow as DF
    return DF.audit_kernels(families)


def audit_gradsync_mesh8(families: Sequence[str] = (GRAPH,)
                         ) -> Optional[Tuple[dict, List[Finding]]]:
    """Compressed cross-pod gradient sync on the (pod, data, model)
    8-device mesh: group-safe shardings for the train state AND the
    element-packed u4 serve tree, scatter-free + callback-free
    compressed-sync step, mask-once under shard_map; numerics: NM301 on
    the step trace and NM304 on the compiled donated step (pod-crossing
    collectives only); buffers: NM401 on the same compiled step.

    NM302 is structurally EXEMPT here: the compressed sync's error-
    feedback residual ``err = g - decode(encode(g))`` intentionally
    round-trips f32→bf16→f32 — that double round IS the PR 6 fix, so
    running the double-round rule on this case would flag the cure as
    the disease.
    """
    fam = set(families)
    if not fam & {GRAPH, NUMERICS, BUFFERS}:
        return None
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.core.sparsity import SparsityConfig
    from repro.launch import spmd
    from repro.models import transformer_lm as T
    from repro.optim import sgd
    from repro.serve.packed_params import pack_tree_element
    from repro.sharding import rules as R
    from repro.train import step as ST

    if jax.device_count() < 8:
        raise RuntimeError(
            "gradsync_mesh8 needs 8 devices — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 or let "
            "tools/nmlint.py --mesh8 force them before backend init")

    cfg = get_arch("qwen3-8b").smoke
    sp = SparsityConfig(n=2, m=8, method="bdwp")
    opt = sgd.SGDConfig(lr=0.05, total_steps=100)
    mesh = spmd.make_spmd_mesh("pod,data,model")

    findings: List[Finding] = []
    # NM204 on the train state: build_lm_train runs assert_nm_unsplit
    # internally — surface a violation as a finding, not a crash.  The
    # bundle donates the state so the SAME compiled artifact serves the
    # NM304 wire audit and the NM401 donation audit.
    try:
        bundle = ST.build_lm_train(cfg, mesh, sp, opt, donate=True,
                                   compress=True)
    except AssertionError as e:
        return ({"arch": "qwen3-8b-smoke", "nm": f"{sp.n}:{sp.m}"},
                [Finding("NM204", "gradsync_mesh8", 0,
                         f"train-state sharding refused: {e}")])

    state = ST.init_train_state(jax.random.PRNGKey(0), cfg, compress=True,
                                sp_cfg=sp, mesh=mesh)
    b0 = _lm_batch(8, 32)
    sites = prunable_sites(state["master"], sp)
    step_args = (_structs(state), _structs(b0))
    step_jaxpr, _ = trace_once(bundle.step_fn, *step_args)
    metrics = {"arch": "qwen3-8b-smoke", "nm": f"{sp.n}:{sp.m}",
               "mesh": {k: int(v) for k, v in dict(mesh.shape).items()},
               "prunable_params": len(sites)}

    hlo = None
    if fam & {NUMERICS, BUFFERS}:
        hlo = bundle.step_fn.lower(*step_args).compile().as_text()

    if GRAPH in fam:
        fs, masks = check_mask_once(step_jaxpr, (), len(sites),
                                    (sp.n, sp.m), "gradsync_mesh8",
                                    "compressed-sync step")
        findings.extend(fs)
        fs, callbacks = check_callback_free(step_jaxpr, (),
                                            "gradsync_mesh8",
                                            "compressed-sync step")
        findings.extend(fs)

        # NM204 on the element-packed u4 serve tree, resolved on this mesh
        aparams, specs = T.init(jax.random.PRNGKey(0), cfg, abstract=True)
        p_pspecs = R.nm_params_pspecs(specs, R.SERVE_BATCH_RULES, aparams,
                                      mesh, sp)
        findings.extend(check_group_integrity(p_pspecs, aparams, mesh, sp,
                                              "gradsync_mesh8"))
        params, _ = T.init(jax.random.PRNGKey(0), cfg)
        params = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
        packed, _, packed_pspecs = pack_tree_element(params, sp,
                                                     pspecs=p_pspecs,
                                                     idx_bits=4)
        findings.extend(check_group_integrity(packed_pspecs, packed, mesh,
                                              sp, "gradsync_mesh8"))
        metrics.update(mask_ops=masks, host_callbacks=callbacks)

    if NUMERICS in fam:
        from repro.analysis import dtype_flow as DF
        fs, selections = DF.check_master_mask_source(
            step_jaxpr, DF.tag_inputs(*step_args), (sp.n, sp.m),
            "gradsync_mesh8", "compressed-sync step")
        findings.extend(fs)
        # NM302 skipped: EF residual double-round is the PR 6 fix (see
        # docstring); NM304 audits only pod-crossing collectives —
        # intra-pod f32 reductions ride the fast fabric legitimately
        pod_block = int(jax.device_count()
                        // int(dict(mesh.shape).get("pod", 1)))
        fs, collectives = DF.check_wire_narrow(
            hlo, "gradsync_mesh8", "compiled compressed-sync step",
            pod_block=pod_block)
        findings.extend(fs)
        metrics["numerics"] = {"selections_inspected": selections,
                               "double_round_checked": False,
                               "collectives_inspected": collectives}

    if BUFFERS in fam:
        from repro.analysis import buffer_audit as BA
        fs, donation = BA.check_donation_aliased(
            hlo, _structs(state), "gradsync_mesh8",
            "donated compressed-sync step")
        findings.extend(fs)
        metrics["buffers"] = donation

    return metrics, findings


CASES = {
    "dense_lm": audit_dense_lm,
    "moe": audit_moe,
    "conv": audit_conv,
    "serve_u4": audit_serve_u4,
    "kernels": audit_kernels,
}
MESH8_CASES = {
    "gradsync_mesh8": audit_gradsync_mesh8,
}


def run_graph_audit(mesh8: bool = False,
                    cases: Optional[Dict] = None,
                    families: Sequence[str] = (GRAPH,)
                    ) -> Tuple[List[Finding], Dict[str, dict]]:
    """Run the config matrix -> (findings, per-case metrics).

    ``families`` selects which rule families each case runs (graph /
    numerics / buffers); a case that covers none of them returns None
    and is skipped entirely."""
    todo = dict(cases) if cases is not None else dict(CASES)
    if cases is None and mesh8:
        todo.update(MESH8_CASES)
    findings: List[Finding] = []
    metrics: Dict[str, dict] = {}
    for name, fn in todo.items():
        res = fn(families=families)
        if res is None:
            continue
        m, fs = res
        metrics[name] = m
        findings.extend(fs)
    return findings, metrics
