"""SparseOperand — the unified N:M weight-consumption algebra.

The paper's SAT accelerator wins because ONE datapath serves both dense
and N:M sparse operations (PAPER.md §IV); this module is that datapath's
software twin.  Every way the system consumes a (possibly N:M-sparse)
weight is one operand type, and every consumer calls one entry point:

    y = nm_apply(op, x, backend=...)

Operand variants (all registered pytrees — they live inside train-state
/ param trees, shard leaf-by-leaf, scan/vmap transparently, and
checkpoint as ordinary leaves):

  DenseOp(w)            dense weight — plain matmul/conv, AMP backward.
  MaskedOp(w, cfg)      legacy in-op masking: FF/BP N:M masks re-derived
                        from ``w`` on every call (bdwp Alg. 1; all five
                        methods incl. sdgp gradient pruning).
  PregenOp(ff|vals+idx, pre-generated WU-time operands (paper Fig. 11c,
           bp, mask,    written by optim/sgd): FF forward on the stored
           cfg,         sparse operand — packed ``(vals, idx)`` consumed
           idx_bits)    straight through ``kernels/nm_spmm`` on the
                        pallas backend, decompressed (select-based, no
                        scatter) on the jnp backend — BP backward on the
                        ``bp`` operand, and the dense straight-through
                        WU gradient riding the ``bp`` cotangent.
  PackedOp(vals, idx,   forward-only element-packed serving weight
           cfg,         (serve/packed_params): ``kernels/nm_spmm``
           idx_bits)    consumes the pair at ~N/M of dense HBM bytes.
  SharedOp(vals, idx)   shared-pattern reduced-K serving weight
                        (bdwp.pack_tree_shared): gather + short matmul.

``idx_bits`` (4 or 8, default 8) names the stored index-plane width on
the two packed operands: 8 = one uint8 in-group offset per kept value,
4 = two offsets per byte (``sparsity.pack_idx_u4`` layout, M <= 16 —
the serving default, worth an extra ~17% off packed HBM bytes at 2:8).
It rides the pytree *aux* (not a leaf), so jit caches key on the index
format and a u4 tree can never be silently consumed as u8.  Both widths
are bitwise interchangeable end-to-end — same matmul, same grads.

Backends: ``backend="auto"`` resolves through the ambient
``backend_scope`` (set by the train-step builders) and then the device —
"pallas" on TPU, "jnp" elsewhere.  The two backends are numerically
interchangeable (the CPU kernel path runs interpret-mode; the tests pin
them bitwise on the suite shapes); the pallas backend is where the
packed HBM saving lands in training wall-clock, because the packed FF
operand never materializes densely outside VMEM.

The custom-VJP rules (FF forward on the sparse operand, BP backward on
the bp operand, dense straight-through WU cotangent) were previously
re-implemented per consumption path in ``core/bdwp.py``; they live here
now, once.  ``bdwp.nm_linear`` / ``nm_conv`` / ``nm_linear_pregen`` /
``nm_conv_pregen`` / ``nm_linear_packed`` remain as deprecation shims.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scopes as S
from repro.core.sparsity import SparsityConfig, sparsify

__all__ = [
    "SparseOperand", "DenseOp", "MaskedOp", "PregenOp", "PackedOp",
    "SharedOp", "as_operand", "nm_apply", "backend_scope",
    "resolve_backend",
]


# ---------------------------------------------------------------------------
# Operand pytrees
# ---------------------------------------------------------------------------
#
# Children are registered in a FIXED alphabetical field order.  This is
# load-bearing for checkpoint forward-compatibility: the PR-3/PR-4-era
# compute trees stored pre-generated operands as plain dicts, which jax
# flattens in sorted-key order — a PregenOp flattens to the same leaf
# sequence, so dict-leaf checkpoints restore leaf-for-leaf (bitwise)
# into operand-typed state with no conversion pass.


class SparseOperand:
    """Base class: field storage + dict-like access (migration aid).

    The dict accessors (``op["bp"]``, ``"vals" in op``, ``op.get``,
    iteration over field names) exist so code and tests written against
    the operand-dict era keep working verbatim; new code should use the
    attributes."""

    _FIELDS: tuple = ()          # class-level ordered field names
    fields: tuple = ()           # instance-level present fields

    # -- dict-like migration accessors -----------------------------------
    def __getitem__(self, key):
        if key in self.fields:
            return getattr(self, key)
        raise KeyError(key)

    def __contains__(self, key) -> bool:
        return key in self.fields

    def __iter__(self):
        return iter(self.fields)

    def keys(self):
        return self.fields

    def get(self, key, default=None):
        return getattr(self, key) if key in self.fields else default

    def __repr__(self):
        body = ", ".join(f"{f}={getattr(self, f)!r}" for f in self.fields)
        return f"{type(self).__name__}({body})"

    # -- pytree plumbing --------------------------------------------------
    def map_children(self, fn):
        """Same operand structure with ``fn`` applied to every child —
        used to build matching PartitionSpec / sharding trees."""
        new = object.__new__(type(self))
        new.__dict__.update(self.__dict__)
        for f in self.fields:
            setattr(new, f, fn(getattr(self, f)))
        return new

    def _aux(self):
        # idx_bits rides the aux so jit caches key on the index format;
        # the 2-tuple form is still accepted by _unflatten (pre-u4
        # pickled treedefs and any external callers keep working)
        return (self.fields, getattr(self, "cfg", None),
                getattr(self, "idx_bits", 8))

    def _children(self):
        return tuple(getattr(self, f) for f in self.fields)

    @classmethod
    def _unflatten(cls, aux, children):
        new = object.__new__(cls)
        new.fields, new.cfg = aux[0], aux[1]
        new.idx_bits = aux[2] if len(aux) > 2 else 8
        for f in cls._FIELDS:
            setattr(new, f, None)
        for f, c in zip(new.fields, children):
            setattr(new, f, c)
        return new


def _register(cls):
    from jax.tree_util import DictKey, register_pytree_with_keys

    register_pytree_with_keys(
        cls,
        lambda op: (tuple((DictKey(f), getattr(op, f)) for f in op.fields),
                    op._aux()),
        cls._unflatten,
        flatten_func=lambda op: (op._children(), op._aux()),
    )
    return cls


@_register
class DenseOp(SparseOperand):
    """A dense weight: no sparsity semantics, AMP forward/backward."""

    _FIELDS = ("w",)

    def __init__(self, w):
        self.fields = ("w",)
        self.w = w
        self.cfg = None


@_register
class MaskedOp(SparseOperand):
    """Legacy in-op masking: masks re-derived from ``w`` per call."""

    _FIELDS = ("w",)

    def __init__(self, w, cfg: SparsityConfig):
        self.fields = ("w",)
        self.w = w
        self.cfg = cfg


@_register
class PregenOp(SparseOperand):
    """Pre-generated WU-time operands (optim/sgd, paper Fig. 11c).

    At most one of ``ff`` (dense-layout bf16 FF operand) or
    ``vals``+``idx`` (SORE-packed FF operand along the contraction axis)
    is present; ``bp`` always is (its cotangent carries the dense
    straight-through WU gradient); ``mask`` is the stored SR-STE decay
    mask (optional).

    With a *transposable* cfg (arXiv 2102.08124: one mask N:M in both
    orientations) a bare ``bp`` operand is also valid — the same stored
    array serves FF and BP, so no separate ``ff`` leaf exists and the
    pregen weight state halves."""

    _FIELDS = ("bp", "ff", "idx", "mask", "vals")  # alphabetical — see above

    def __init__(self, *, bp, ff=None, vals=None, idx=None, mask=None,
                 cfg: SparsityConfig | None = None, idx_bits: int = 8):
        transposable = cfg is not None and getattr(cfg, "transposable", False)
        if ff is not None and vals is not None:
            raise ValueError("PregenOp needs at most one of ff | (vals, idx)")
        if ff is None and vals is None and not transposable:
            raise ValueError("PregenOp needs exactly one of ff | (vals, idx)"
                             " (bp-only operands require a transposable cfg)")
        if (vals is None) != (idx is None):
            raise ValueError("PregenOp packed form needs both vals and idx")
        if idx_bits not in (4, 8):
            raise ValueError(f"idx_bits must be 4 or 8, got {idx_bits}")
        present = {"bp": bp, "ff": ff, "idx": idx, "mask": mask, "vals": vals}
        self.fields = tuple(f for f in self._FIELDS
                            if present[f] is not None)
        for f in self._FIELDS:
            setattr(self, f, present[f])
        self.cfg = cfg
        self.idx_bits = idx_bits

    @property
    def is_packed(self) -> bool:
        return "vals" in self.fields

    @property
    def is_transposable(self) -> bool:
        return self.cfg is not None and getattr(self.cfg, "transposable",
                                                False)


@_register
class PackedOp(SparseOperand):
    """Forward-only element-packed serving weight (serve/packed_params).

    vals (…, K·N/M, F) surviving values; idx the uint8 in-group offset
    plane — same shape as vals with ``idx_bits=8``, or the u4-packed
    plane (…, ceil(K·N/M / 2), F) with ``idx_bits=4`` (two offsets per
    byte, ``core.sparsity.pack_idx_u4`` layout — half the index HBM
    traffic).  Consumed through ``kernels/nm_spmm``; ``idx_bits`` rides
    the pytree aux, so both formats dispatch through ``nm_apply``
    unchanged."""

    _FIELDS = ("idx", "vals")  # alphabetical

    def __init__(self, vals, idx, cfg: SparsityConfig, idx_bits: int = 8):
        if idx_bits not in (4, 8):
            raise ValueError(f"idx_bits must be 4 or 8, got {idx_bits}")
        self.fields = ("idx", "vals")
        self.vals = vals
        self.idx = idx
        self.cfg = cfg
        self.idx_bits = idx_bits

    @property
    def shape(self) -> tuple:
        """Dense-equivalent weight shape the pair decompresses to."""
        kc = self.vals.shape[-2]
        return (*self.vals.shape[:-2],
                kc * self.cfg.m // self.cfg.n, self.vals.shape[-1])


@_register
class SharedOp(SparseOperand):
    """Shared-pattern reduced-K serving weight (bdwp.pack_tree_shared):
    vals (…, Kc, F) pre-gathered rows, idx (…, Kc) absolute K indices —
    the forward is a gather + an M/N×-shorter matmul."""

    _FIELDS = ("idx", "vals")

    def __init__(self, vals, idx):
        self.fields = ("idx", "vals")
        self.vals = vals
        self.idx = idx
        self.cfg = None


def is_operand(leaf) -> bool:
    return isinstance(leaf, SparseOperand)


def as_operand(leaf, name: str, cfg: SparsityConfig) -> SparseOperand:
    """Coerce any consumption-path leaf format into a SparseOperand.

    Accepts operands (returned as-is), plain weight arrays (→ MaskedOp
    with per-param eligibility via ``bdwp.pick_cfg``), and the legacy
    dict formats: pre-generated operand dicts (→ PregenOp), element-
    packed serve dicts (idx rank == vals rank → PackedOp) and shared-
    packed dicts (per-row idx → SharedOp)."""
    if isinstance(leaf, SparseOperand):
        return leaf
    if isinstance(leaf, dict):
        if "bp" in leaf and ("ff" in leaf or "vals" in leaf):
            # legacy dicts predate the u4 plane: always byte-wide indices
            return PregenOp(bp=leaf["bp"], ff=leaf.get("ff"),
                            vals=leaf.get("vals"), idx=leaf.get("idx"),
                            mask=leaf.get("mask"), cfg=cfg, idx_bits=8)
        if "vals" in leaf and "idx" in leaf:
            if leaf["idx"].ndim == leaf["vals"].ndim:
                return PackedOp(leaf["vals"], leaf["idx"], cfg, idx_bits=8)
            return SharedOp(leaf["vals"], leaf["idx"])
        raise TypeError(f"unrecognized operand dict for {name}: "
                        f"{sorted(leaf)}")
    from repro.core import bdwp  # runtime import: bdwp imports this module

    return MaskedOp(leaf, bdwp.pick_cfg(name, leaf.shape, cfg))


# ---------------------------------------------------------------------------
# Backend selection
# ---------------------------------------------------------------------------

_BACKENDS = ("auto", "jnp", "pallas")
_SCOPE = {"backend": None}


@contextlib.contextmanager
def backend_scope(backend: str):
    """Ambient backend for ``nm_apply(backend="auto")`` calls — the step
    builders enter this around model tracing so one flag switches every
    packed consumption site in the forward.

    The scope is consulted at TRACE time only: a function jitted while
    one scope was ambient keeps that backend in its compiled cache —
    re-entering a different scope does not retrace it.  To switch
    backends, build a fresh jitted function per backend (what the step
    builders' ``nm_backend=`` flag does) or pass ``backend=``
    explicitly."""
    if backend not in _BACKENDS:
        raise ValueError(f"unknown nm_apply backend {backend!r}")
    old = _SCOPE["backend"]
    _SCOPE["backend"] = backend
    try:
        yield
    finally:
        _SCOPE["backend"] = old


def resolve_backend(backend: str = "auto") -> str:
    if backend not in _BACKENDS:
        raise ValueError(f"unknown nm_apply backend {backend!r}")
    if backend == "auto" and _SCOPE["backend"] not in (None, "auto"):
        backend = _SCOPE["backend"]
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


# ---------------------------------------------------------------------------
# Custom-VJP cores — matmul view: x (..., K) @ w (K, F) -> (..., F)
# ---------------------------------------------------------------------------
#
# These carry the paper's training semantics (Alg. 1 / Fig. 11c), moved
# verbatim from core/bdwp.py so every operand type shares one set of
# rules:
#   FF : y  = x @ w_FF          (sparse operand)
#   BP : dx = g @ w_BP^T        (bp operand / re-derived BP mask)
#   WU : dW = x^T @ g           (always dense, straight-through)


def _ff_weights(w: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """FF-pruned weights: N:M groups along the input (contraction) axis."""
    if cfg.prunes_ff_weights():
        return sparsify(w, cfg, axis=0, share_axis=1)
    return w


def _bp_weights(w: jax.Array, cfg: SparsityConfig) -> jax.Array:
    """BP-pruned weights: N:M groups along the output axis (dgrad)."""
    if cfg.prunes_bp_weights():
        return sparsify(w, cfg, axis=1, share_axis=0)
    return w


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def masked_linear(x: jax.Array, w: jax.Array, cfg: SparsityConfig):
    """y = x @ w with cfg.method's N:M sparse training semantics."""
    with jax.named_scope(S.FF):
        return jnp.matmul(x, _ff_weights(w, cfg).astype(x.dtype))


def _masked_linear_fwd(x, w, cfg):
    with jax.named_scope(S.FF):
        y = jnp.matmul(x, _ff_weights(w, cfg).astype(x.dtype))
    return y, (x, w)


def _masked_linear_bwd(cfg, res, g):
    x, w = res
    # AMP dataflow (paper Fig. 11): BP/WU arithmetic runs in the compute
    # dtype (bf16 here, FP16 on SAT); only the weight-gradient *result*
    # accumulates in fp32 for WUVE.  Casting the cotangent down — rather
    # than the weights up — keeps backward activations, remat recompute
    # and the TP collectives in 16-bit (2x traffic saving, and faithful).
    gc = g.astype(x.dtype)
    with jax.named_scope(S.BP):
        if cfg.prunes_bp_grads():  # SDGP: prune the *output gradients* N:M
            g_bp = sparsify(gc, cfg, axis=-1)
            dx = jnp.matmul(g_bp, w.T.astype(gc.dtype))
        else:
            w_bp = _bp_weights(w, cfg)
            dx = jnp.matmul(gc, w_bp.T.astype(gc.dtype))
        dx = dx.reshape(x.shape).astype(x.dtype)
    # WU: dense (paper Alg. 1 line 9), straight-through; fp32 accumulation
    with jax.named_scope(S.WU):
        x2 = x.reshape(-1, x.shape[-1])
        g2 = gc.reshape(-1, gc.shape[-1])
        dw = jnp.matmul(x2.T, g2, preferred_element_type=jnp.float32)
        dw = dw.astype(w.dtype)
    return dx, dw


masked_linear.defvjp(_masked_linear_fwd, _masked_linear_bwd)


@jax.custom_vjp
def pregen_linear(x: jax.Array, ff: jax.Array, bp: jax.Array) -> jax.Array:
    """y = x @ ff with BP on ``bp`` and the dense WU gradient riding the
    ``bp`` cotangent (always dense-shaped)."""
    with jax.named_scope(S.FF):
        return jnp.matmul(x, ff.astype(x.dtype))


def _pregen_linear_fwd(x, ff, bp):
    with jax.named_scope(S.FF):
        return jnp.matmul(x, ff.astype(x.dtype)), (x, ff, bp)


def _pregen_linear_bwd(res, g):
    x, ff, bp = res
    gc = g.astype(x.dtype)
    with jax.named_scope(S.BP):
        dx = jnp.matmul(gc, bp.T.astype(gc.dtype))
        dx = dx.reshape(x.shape).astype(x.dtype)
    with jax.named_scope(S.WU):
        x2 = x.reshape(-1, x.shape[-1])
        g2 = gc.reshape(-1, gc.shape[-1])
        dw = jnp.matmul(x2.T, g2, preferred_element_type=jnp.float32)
        dw = dw.astype(bp.dtype)
    return dx, jnp.zeros_like(ff), dw


pregen_linear.defvjp(_pregen_linear_fwd, _pregen_linear_bwd)


def _spmm_stacked(x2, vals, idx, n: int, m: int, use_pallas: bool,
                  idx_bits: int = 8):
    """kernels/nm_spmm over optionally-stacked packed weights.

    x2 (*stack, T, K), vals/idx (*stack, Kc, F) — vmaps the kernel over
    the leading stack axes (MoE expert stacks ride the same kernel).
    ``idx_bits=4`` hands the kernel the u4 index plane unchanged."""
    from repro.kernels import ops  # local import to avoid cycles

    if vals.ndim == 2:
        return ops.nm_spmm(x2, vals, idx, n, m, use_pallas=use_pallas,
                           idx_bits=idx_bits)
    return jax.vmap(
        lambda xe, ve, ie: _spmm_stacked(xe, ve, ie, n, m, use_pallas,
                                         idx_bits)
    )(x2, vals, idx)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def packed_pregen_linear(x, vals, idx, bp, n: int, m: int,
                         use_pallas: bool = True, idx_bits: int = 8):
    """Packed-FF pre-generated matmul: the forward consumes the SORE
    pair ``(vals, idx)`` directly through ``kernels/nm_spmm`` — the
    dense FF layout never materializes in HBM — while BP/WU follow the
    pregen rules (BP on ``bp``, dense straight-through WU cotangent on
    ``bp``; the uint8 indices get a float0 cotangent).

    Shapes: x (*stack, ..., K), vals/idx (*stack, Kc, F), bp
    (*stack, K, F); token dims between stack and K are flattened for the
    kernel and restored after.  ``idx_bits=4``: idx is the u4 plane
    (*stack, ceil(Kc/2), F).
    """
    y, _ = _packed_pregen_fwd(x, vals, idx, bp, n, m, use_pallas, idx_bits)
    return y


def _packed_pregen_fwd(x, vals, idx, bp, n, m, use_pallas, idx_bits=8):
    stack = vals.ndim - 2
    with jax.named_scope(S.FF):
        x2 = x.reshape(*x.shape[:stack], -1, x.shape[-1])
        y = _spmm_stacked(x2, vals, idx, n, m, use_pallas, idx_bits)
        y = y.reshape(*x.shape[:-1], vals.shape[-1]).astype(x.dtype)
    return y, (x, vals, idx, bp)


def _packed_pregen_bwd(n, m, use_pallas, idx_bits, res, g):
    x, vals, idx, bp = res
    stack = bp.ndim - 2
    gc = g.astype(x.dtype)
    # BP: batched over the stack axes — identical arithmetic to the
    # (vmapped) pregen_linear backward
    g2 = gc.reshape(*gc.shape[:stack], -1, gc.shape[-1])
    x2 = x.reshape(*x.shape[:stack], -1, x.shape[-1])
    with jax.named_scope(S.BP):
        bp_t = jnp.swapaxes(bp, -1, -2).astype(gc.dtype)
        dx = jnp.matmul(g2, bp_t).reshape(x.shape).astype(x.dtype)
    # WU: dense straight-through, fp32-accumulated, on the bp cotangent
    with jax.named_scope(S.WU):
        dw = jnp.matmul(jnp.swapaxes(x2, -1, -2), g2,
                        preferred_element_type=jnp.float32).astype(bp.dtype)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dx, jnp.zeros_like(vals), didx, dw


packed_pregen_linear.defvjp(_packed_pregen_fwd, _packed_pregen_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def packed_pregen_linear_t(x, vals, idx, bp, n: int, m: int,
                           use_pallas: bool = True, idx_bits: int = 8):
    """Transposable-mask packed matmul (arXiv 2102.08124): the ONE
    stored mask is N:M along both the contraction and the output axis,
    so the packed ``(vals, idx)`` pair serves FF *and* BP.  The forward
    is ``packed_pregen_linear``'s (nm_spmm on the pair); dgrad
    decompresses the pair (select-based, exact — decompressed == the
    dense ``bp`` copy bitwise, same mask, same bf16 values) and
    contracts g @ w^T instead of reading ``bp``.  ``bp`` therefore only
    carries the dense straight-through WU gradient on its cotangent —
    no op ever reads the array, so the lowered step loads one weight
    operand per layer instead of two."""
    y, _ = _packed_pregen_fwd(x, vals, idx, bp, n, m, use_pallas, idx_bits)
    return y


def _packed_pregen_t_bwd(n, m, use_pallas, idx_bits, res, g):
    x, vals, idx, bp = res
    from repro.kernels.nm_spmm_shared import decompress_nm

    stack = bp.ndim - 2
    gc = g.astype(x.dtype)
    g2 = gc.reshape(*gc.shape[:stack], -1, gc.shape[-1])
    x2 = x.reshape(*x.shape[:stack], -1, x.shape[-1])
    with jax.named_scope(S.BP):
        w_bp = decompress_nm(vals, idx, n, m, axis=-2, idx_bits=idx_bits)
        dx = jnp.matmul(g2, jnp.swapaxes(w_bp, -1, -2).astype(gc.dtype))
        dx = dx.reshape(x.shape).astype(x.dtype)
    with jax.named_scope(S.WU):
        dw = jnp.matmul(jnp.swapaxes(x2, -1, -2), g2,
                        preferred_element_type=jnp.float32).astype(bp.dtype)
    didx = np.zeros(idx.shape, dtype=jax.dtypes.float0)
    return dx, jnp.zeros_like(vals), didx, dw


packed_pregen_linear_t.defvjp(_packed_pregen_fwd, _packed_pregen_t_bwd)


# ---------------------------------------------------------------------------
# Custom-VJP cores — conv view (NHWC x HWIO -> NHWC)
# ---------------------------------------------------------------------------

_CONV_IN_AXIS = 2   # HWIO: input-channel axis (FF grouping, Fig. 5a)
_CONV_OUT_AXIS = 3  # HWIO: output-channel axis (BP grouping, Fig. 5b)


def _conv(x, w, stride, padding):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def masked_conv(x, w, cfg: SparsityConfig, stride: int = 1,
                padding: str = "SAME"):
    w_ff = sparsify(w, cfg, axis=_CONV_IN_AXIS, share_axis=_CONV_OUT_AXIS) \
        if cfg.prunes_ff_weights() else w
    return _conv(x, w_ff, stride, padding)


def _masked_conv_fwd(x, w, cfg, stride, padding):
    w_ff = sparsify(w, cfg, axis=_CONV_IN_AXIS, share_axis=_CONV_OUT_AXIS) \
        if cfg.prunes_ff_weights() else w
    return _conv(x, w_ff, stride, padding), (x, w)


def _masked_conv_bwd(cfg, stride, padding, res, g):
    x, w = res
    if cfg.prunes_bp_grads():
        g_eff = sparsify(g, cfg, axis=-1)  # N:M across output channels
        w_bp = w
    else:
        g_eff = g
        w_bp = sparsify(w, cfg, axis=_CONV_OUT_AXIS, share_axis=_CONV_IN_AXIS) \
            if cfg.prunes_bp_weights() else w
    # dgrad through a closure over the BP weights
    _, dgrad = jax.vjp(lambda xx: _conv(xx, w_bp, stride, padding), x)
    (dx,) = dgrad(g_eff.astype(x.dtype))
    # wgrad dense (straight-through to master weights)
    _, wgrad = jax.vjp(lambda ww: _conv(x, ww, stride, padding), w)
    (dw,) = wgrad(g.astype(x.dtype))
    return dx, dw.astype(w.dtype)


masked_conv.defvjp(_masked_conv_fwd, _masked_conv_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pregen_conv(x, ff, bp, stride: int = 1, padding: str = "SAME"):
    """Conv view of ``pregen_linear``: FF convolves the WU-time FF
    operand, dgrad convolves ``bp``, wgrad is dense straight-through on
    the BP operand's cotangent."""
    return _conv(x, ff, stride, padding)


def _pregen_conv_fwd(x, ff, bp, stride, padding):
    return _conv(x, ff, stride, padding), (x, ff, bp)


def _pregen_conv_bwd(stride, padding, res, g):
    x, ff, bp = res
    _, dgrad = jax.vjp(lambda xx: _conv(xx, bp, stride, padding), x)
    (dx,) = dgrad(g.astype(x.dtype))
    _, wgrad = jax.vjp(lambda ww: _conv(x, ww, stride, padding), bp)
    (dw,) = wgrad(g.astype(x.dtype))
    return dx, jnp.zeros_like(ff), dw.astype(bp.dtype)


pregen_conv.defvjp(_pregen_conv_fwd, _pregen_conv_bwd)


# ---------------------------------------------------------------------------
# Forward-only serving consumption
# ---------------------------------------------------------------------------


def _packed_serve(x, op: PackedOp, backend: str):
    """Element-packed serving matmul through kernels/nm_spmm.

    Leading stack axes on the pair (layer-stacked leaves consumed
    outside the scan) vmap through the kernel, same as the packed
    training path."""
    stack = op.vals.ndim - 2
    x2 = x.reshape(*x.shape[:stack], -1, x.shape[-1])
    y = _spmm_stacked(x2, op.vals, op.idx, op.cfg.n, op.cfg.m,
                      backend == "pallas", op.idx_bits)
    return y.reshape(*x.shape[:-1], op.vals.shape[-1]).astype(x.dtype)


def _shared_serve(x, op: SharedOp):
    """Shared-pattern reduced-K matmul: gather survivors, contract Kc."""
    xg = jnp.take(x, op.idx, axis=-1)
    return jnp.matmul(xg, op.vals.astype(xg.dtype))


# ---------------------------------------------------------------------------
# The dispatch
# ---------------------------------------------------------------------------


def _pregen_ff_dense(op: PregenOp) -> jax.Array:
    """Dense-layout FF operand of a PregenOp (decompressing packed
    leaves with the shared select-based helper — exact, scatter-free).
    Transposable bp-only operands FF on ``bp`` itself: the one mask is
    N:M in both orientations, so the same array is the FF operand."""
    if op.ff is not None:
        return op.ff
    if op.is_packed:
        from repro.kernels.nm_spmm_shared import decompress_nm

        cfg = op.cfg
        return decompress_nm(op.vals, op.idx, cfg.n, cfg.m, axis=-2,
                             idx_bits=op.idx_bits)
    return op.bp


def nm_apply(op, x: jax.Array, *, backend: str = "auto",
             stacked: bool = False, stride: int = 1,
             padding: str = "SAME") -> jax.Array:
    """Apply one operand to activations — THE N:M consumption seam.

    Dispatch:
      * matmul view for rank-2 weights (rank-3 with ``stacked=True``:
        the leading axis is a vmapped expert/stack axis — N:M groups
        stay within one expert);
      * conv view (NHWC x HWIO) for rank-4 weights, with ``stride`` /
        ``padding``;
      * ``backend`` picks how packed ``(vals, idx)`` pairs are consumed:
        "pallas" streams them through ``kernels/nm_spmm`` (interpret
        mode off-TPU), "jnp" decompresses in-register (select-based, no
        scatter) and runs the dense-layout matmul; "auto" defers to the
        ambient ``backend_scope`` then the device;
      * the operand's ``idx_bits`` flows through unchanged — a u4 index
        plane is expanded inside the kernel tile (pallas) or unpacked
        nibble-first before the in-register decompress (jnp); the two
        widths are bitwise interchangeable.

    Gradient semantics ride the operand type: MaskedOp re-derives masks
    per cfg.method; PregenOp backs through ``bp`` with the dense
    straight-through WU cotangent; PackedOp/SharedOp are forward-only
    serving paths.
    """
    backend = resolve_backend(backend)

    if isinstance(op, DenseOp):
        from repro.core.sparsity import DENSE

        op = MaskedOp(op.w, DENSE)

    if isinstance(op, MaskedOp):
        w, cfg = op.w, op.cfg
        if w.ndim == 4 and not stacked:
            return masked_conv(x, w, cfg, stride, padding)
        if stacked:
            return jax.vmap(lambda xe, we: masked_linear(xe, we, cfg))(x, w)
        return masked_linear(x, w, cfg)

    if isinstance(op, PregenOp):
        if op.bp.ndim == 4 and not stacked:  # conv: HWIO operands
            return pregen_conv(x, _pregen_ff_dense(op), op.bp,
                               stride, padding)
        if op.is_packed and backend == "pallas":
            cfg = op.cfg
            fn = packed_pregen_linear_t if op.is_transposable \
                else packed_pregen_linear
            return fn(x, op.vals, op.idx, op.bp, cfg.n, cfg.m, True,
                      op.idx_bits)
        with jax.named_scope(S.FF):  # decompressing a packed FF operand
            ff = _pregen_ff_dense(op)
        if stacked:
            return jax.vmap(pregen_linear)(x, ff, op.bp)
        return pregen_linear(x, ff, op.bp)

    if isinstance(op, PackedOp):
        return _packed_serve(x, op, backend)

    if isinstance(op, SharedOp):
        return _shared_serve(x, op)

    raise TypeError(f"nm_apply: not a SparseOperand: {type(op).__name__}")
