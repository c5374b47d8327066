"""Cross-pod gradient sync with natively-N:M payloads, off the critical
path.

On a multi-pod mesh the "pod" axis rides the slow inter-pod links.  We
apply the paper's own primitive — keep N of every M-group — to the
*gradients* crossing that axis, shipping packed (bf16 vals, uint8 idx)
instead of dense fp32.  Two estimators:

  * ``topk``  — largest-|g| per group with an error-feedback residual
    (Karimireddy et al., 2019); the fused kernel folds the bf16 wire
    rounding into the residual, so sum(decoded) + err telescopes to
    sum(g) exactly in fp32.
  * ``mvue``  — the minimum-variance unbiased estimator of arXiv
    2203.10991: water-filled inclusion probabilities p = min(1, |g|/τ)
    with Στ p = n per group, systematic sampling (exactly n draws), and
    1/p rescaling.  Unbiased per step — no residual state — and exact
    whenever a group has ≤ n nonzeros.

Dataflow (the paper's pre-generation argument, Fig. 11c, applied to the
network): the train step computes per-pod mean gradients by vmapping
value_and_grad over a pod-stacked parameter copy, so GSPMD's implicit
gradient all-reduce stays *inside* a pod ("data" groups only).  This
module then flattens each device's LOCAL blocks of the compressible
leaves into one device-local slab (no pre-gather: a device compresses
only the T_loc elements it already holds) and walks it in m-aligned
buckets inside a MANUAL shard_map — the compress math (fused
kernels/grad_compress, no dense intermediates) is purely local so the
GSPMD partitioner can never reshard inside it, and each bucket ends in
one explicit packed (vals, idx) collective over "pod": the only traffic
that crosses pods.  The walk is one ``lax.scan`` over equal buckets, so
the program holds a single compress + exchange + decode body whatever
the slab size: neither compile time nor the sync's scratch memory grows
with the bucket count.  The payload ships vals bitcast to uint16 — XLA
would otherwise hoist the decoder's bf16→f32 convert above the
collective and double the wire bytes.  For the topk estimator on a
two-pod mesh the hop is a ppermute *exchange* rather than an
all_gather: error feedback already gives each pod its own decoded
payload for free (decode(own) == (g+err) - new_err bit-for-bit, the
bf16 rounding being Sterbenz-exact in f32), so only the peer's row pays
the one-hot decode.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sparsity import (
    SparsityConfig,
    _topn_group_mask,
    nm_mask,
    nm_pack_from_mask,
)
from repro.kernels import ops
from repro.kernels.ref import bf16_round
from repro.sharding import rules as R


def compress_leaf(g, err, n: int, m: int):
    """N:M-sparsify g+err along the last axis; returns (sparse, new_err).

    The returned sparse tensor holds what the wire ACTUALLY carries —
    the kept values rounded to bf16 (the packed exchange transmits
    bf16) — and the residual absorbs both the pruned values AND that
    rounding error, so sum(sent) + err telescopes to sum(g) exactly in
    fp32 (pinned by tests/test_spmd.py).  This is the single-leaf
    reference semantics; the bucketed sync path below uses the fused
    kernel equivalent (kernels/grad_compress).
    """
    size = g.size
    if size % m != 0 or g.ndim == 0:
        return g, err  # tiny/ragged leaves ride uncompressed
    flat = (g + err).reshape(-1, m)
    mask = nm_mask(flat, n, m, axis=-1)
    sent = bf16_round(jnp.where(mask, flat, 0.0).astype(jnp.float32))
    new_err = (flat - sent).reshape(g.shape)
    return sent.reshape(g.shape), new_err


# ---------------------------------------------------------------------------
# Config + bucket planning
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class GradCompressConfig:
    """Knobs for the bucketed cross-pod sync.

    bucket_elems must be a multiple of m: a bucket boundary inside an
    M-group would split the group's top-N selection across two buckets
    (and two collectives), silently changing the estimator — refused at
    construction, and again by ``plan_buckets`` for ad-hoc splits.  A
    bucket is one scan iteration: its width sets the sync's scratch
    memory (~100 B per element, by a compile for TPU v5e), the slab's
    size sets the trip count (1 Mi elements: ~130 trips for a device's
    share of the 4-layer granite-moe-1b-a400m step on pod=2,model=2).
    """

    n: int = 2
    m: int = 8
    estimator: str = "topk"       # "topk" (EF) | "mvue" (unbiased, no EF)
    bucket_elems: int = 1 << 20
    use_pallas: bool = False

    def __post_init__(self):
        if self.estimator not in ("topk", "mvue"):
            raise ValueError(f"unknown gradient estimator {self.estimator!r}")
        if self.bucket_elems <= 0 or self.bucket_elems % self.m:
            raise ValueError(
                f"bucket_elems={self.bucket_elems} would split an M-group "
                f"(m={self.m}): bucket boundaries must be M-aligned")

    @classmethod
    def from_sparsity(cls, sp_cfg: SparsityConfig, **kw):
        return cls(n=sp_cfg.n, m=sp_cfg.m, **kw)


def compressible_shape(shape, m: int) -> bool:
    """Leaves whose flat size is a whole number of M-groups ride packed;
    scalars and ragged leaves (e.g. a (3,) bias) ride dense."""
    size = math.prod(shape)
    return len(shape) > 0 and size > 0 and size % m == 0


def slab_shards(mesh: Mesh) -> int:
    """S — how many distinct local slabs exist per pod (the intra-pod
    device count): each device compresses only the leaf blocks it
    already holds instead of redoing the whole slab's top-k selection."""
    return int(math.prod(s for a, s in mesh.shape.items() if a != "pod"))


def local_block_shape(shape, spec, mesh: Mesh):
    """A leaf's per-device block shape under its PartitionSpec."""
    entries = tuple(spec) if spec is not None else ()
    out = []
    for i, d in enumerate(shape):
        e = entries[i] if i < len(entries) else None
        split = 1
        if e is not None:
            for ax in (e if isinstance(e, tuple) else (e,)):
                split *= mesh.shape[ax]
        if d % split:
            raise ValueError(f"dim {d} of {shape} not divisible by its "
                             f"{split}-way shard ({spec})")
        out.append(d // split)
    return tuple(out)


def _slab_layout(shapes, specs, mesh: Mesh, m: int):
    """(per-compressible-leaf local sizes, T_loc, T_loc padded to m).

    The sync slab is DEVICE-LOCAL: each device flattens the leaf blocks
    it already holds, in tree order.  SPMD keeps block shapes uniform
    across devices, so T_loc is one number; leaves replicated along some
    intra-pod axis appear in several devices' slabs (benign duplicate
    compute, consistent results — the compressor is deterministic).
    """
    loc = []
    for shape, spec in zip(shapes, specs):
        if not compressible_shape(shape, m):
            continue
        if mesh is None:
            loc.append(math.prod(shape))
        else:
            loc.append(math.prod(local_block_shape(shape, spec, mesh)))
    t_loc = sum(loc)
    return loc, t_loc, (t_loc + m - 1) // m * m


def err_state_elems(params, m: int, mesh: Mesh = None,
                    grad_pspecs=None) -> int:
    """Width of the (n_pods, ·) error-feedback slab.

    Each device carries its own EF residual over its local slab (the
    leaf blocks it holds, padded to whole M-groups), so the global state
    is T_loc_pad * S wide — S local slabs per pod laid out along the
    intra-pod axes.  Without a mesh (or specs) everything is one
    device's slab: the plain padded compressible total.  Padding is
    benign: a zero group compresses to zero payload and zero residual.
    """
    leaves = jax.tree_util.tree_leaves(params)
    shapes = [p.shape for p in leaves]
    if mesh is None or grad_pspecs is None:
        _, _, t_pad = _slab_layout(shapes, [None] * len(shapes), None, m)
        return t_pad
    specs = jax.tree_util.tree_flatten(
        grad_pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    _, _, t_pad = _slab_layout(shapes, specs, mesh, m)
    return t_pad * slab_shards(mesh)


def plan_buckets(total: int, bucket_elems: int, m: int):
    """Static (start, stop) schedule over the flat slab.

    Every boundary is M-aligned (bucket_elems % m == 0, and total is a
    sum of M-divisible leaf sizes); a split that would cross a group is
    refused rather than rounded.
    """
    if bucket_elems <= 0 or bucket_elems % m:
        raise ValueError(
            f"bucket_elems={bucket_elems} would split an M-group (m={m})")
    if total % m:
        raise ValueError(f"slab of {total} elems is not M-divisible (m={m})")
    return [(s, min(s + bucket_elems, total))
            for s in range(0, total, bucket_elems)]


# ---------------------------------------------------------------------------
# MVUE estimator (arXiv 2203.10991), jnp path
# ---------------------------------------------------------------------------


def mvue_probs(a: jax.Array, n: int) -> jax.Array:
    """Water-filled inclusion probabilities per group.

    a: (..., m) nonnegative scores.  Returns p = min(1, a/τ) with τ
    chosen so Σ p = n (when the group has ≥ n nonzeros; fewer nonzeros
    get p = 1 each — the estimator is exact there).  The fixed point is
    reached in ≤ n rounds: each round at most (n - |saturated|) entries
    can newly saturate, and τ is non-increasing.
    """
    sat = jnp.zeros(a.shape, bool)
    tau = jnp.sum(a, -1, keepdims=True) / n
    for _ in range(n):
        denom = n - jnp.sum(sat, -1, keepdims=True)
        rest = jnp.where(sat, 0.0, a).sum(-1, keepdims=True)
        ok = denom > 0
        tau = jnp.where(ok, rest / jnp.maximum(denom, 1), tau)
        sat = jnp.where(ok, a >= tau, sat)
    p = jnp.where(sat, 1.0,
                  jnp.where(tau > 0, a / jnp.maximum(tau, 1e-38), 0.0))
    return jnp.where(a > 0, jnp.clip(p, 0.0, 1.0), 0.0)


def _systematic_sample(p: jax.Array, key) -> jax.Array:
    """Exactly-⌊Σp⌋-ish draws per group via one shared uniform offset:
    position i is selected iff ⌊c_i - u⌋ > ⌊c_{i-1} - u⌋ on the cumsum
    c.  Every p=1 entry is always selected; total draws ≤ n when Σp ≤ n.
    """
    c = jnp.cumsum(p, axis=-1)
    u = jax.random.uniform(key, c.shape[:-1] + (1,), dtype=c.dtype)
    f = jnp.floor(c - u)
    prev = jnp.concatenate(
        [jnp.broadcast_to(jnp.floor(-u), f[..., :1].shape), f[..., :-1]],
        axis=-1)
    return f > prev


def mvue_compress(t: jax.Array, n: int, m: int, key):
    """(..., L) -> packed (bf16 vals, uint8 idx) along the last axis.

    Selected values are rescaled by 1/p (unbiased before the bf16 wire
    rounding).  Groups short of n draws are padded with earliest-index
    zero-probability slots (value 0 — the estimate is unchanged) so the
    payload always holds exactly n slots per group.
    """
    g = t.reshape(*t.shape[:-1], t.shape[-1] // m, m).astype(jnp.float32)
    p = mvue_probs(jnp.abs(g), n)
    sel = _systematic_sample(p, key)
    mask = _topn_group_mask(jnp.where(sel, 1.0, 0.0), n)
    est = jnp.where(sel, g / jnp.maximum(p, 1e-38), 0.0)
    vals, idx = nm_pack_from_mask(est.reshape(t.shape),
                                  mask.reshape(t.shape), n, m, axis=-1)
    return vals.astype(jnp.bfloat16), idx


# ---------------------------------------------------------------------------
# The bucketed cross-pod sync
# ---------------------------------------------------------------------------


def cross_pod_sync(grads, err, mesh: Mesh, grad_pspecs,
                   cfg: GradCompressConfig, key=None):
    """Pod-mean of pod-stacked gradients with packed N:M payload.

    grads: master-structured tree of pod-stacked leaves (n_pods, *shape)
    — each pod's own data-mean gradient (the vmapped train step keeps
    GSPMD's gradient all-reduce intra-pod).  err: the fp32 EF residual
    slab (``err_state_elems`` wide).  Returns (master-shaped mean grads,
    new err).

    The whole walk runs inside one manual shard_map over DEVICE-LOCAL
    slabs: each device flattens the leaf blocks it already holds under
    the master shardings into a (1, T_loc) slab, compresses it bucket by
    bucket, and the ONLY pod-crossing traffic is each bucket's packed
    (bf16 vals bitcast to u16, u8 idx) payload — a tiled all_gather in
    general, a ppermute exchange on the two-pod topk fast path (the own
    pod's decode comes free from the EF identity).  Because the pod
    axis is the mesh's outermost, corresponding devices across pods hold
    blocks of the SAME leaf slices, so the gathered payloads decode into
    that device's own shard of the pod-mean gradient — there is no
    global slab to assemble, no leaf re-replication before compressing,
    and no redistribution collective afterwards.  Ragged leaves ride a
    dense fp32 pmean over "pod".  The buckets are the iterations of one
    scan; the last is zero-padded to full width.
    """
    n_pods = mesh.shape["pod"]
    n, m = cfg.n, cfg.m
    shards = slab_shards(mesh)
    err_spec = R.grad_sync_pspecs(mesh)["err"]

    flat_g, tdef = jax.tree_util.tree_flatten(grads)
    flat_s = jax.tree_util.tree_flatten(
        grad_pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    comp = [compressible_shape(g.shape[1:], m) for g in flat_g]
    _, t_loc, t_loc_pad = _slab_layout(
        [g.shape[1:] for g in flat_g], flat_s, mesh, m)
    if err.shape != (n_pods, t_loc_pad * shards):
        raise ValueError(
            f"EF residual shape {err.shape} != "
            f"(n_pods={n_pods}, {t_loc_pad * shards}) — init the train "
            "state against the same master tree/specs/mesh")
    if key is None:
        key = jax.random.PRNGKey(0)
    # equal buckets, none wider than the slab, walked by one scan body
    bucket = min(cfg.bucket_elems, t_loc_pad)
    n_buckets = len(plan_buckets(t_loc_pad, bucket, m)) if t_loc_pad else 0

    def sync_bucket(k, _, xs):
        b, gb, ebk = xs
        if cfg.estimator == "mvue":
            vals, idx = mvue_compress(gb, n, m, jax.random.fold_in(k, b))
            new_eb = ebk  # unbiased estimator: no residual
        else:
            vals, idx, new_eb = ops.grad_compress(
                gb, ebk, n, m, use_pallas=cfg.use_pallas)
        # ship vals bitcast to u16: XLA otherwise hoists the decoder's
        # bf16->f32 convert above the collective and doubles the wire
        # bytes of the hop
        wire = jax.lax.bitcast_convert_type(vals, jnp.uint16)
        if cfg.estimator == "topk" and n_pods == 2:
            # EF telescoping gives the own pod's decoded payload for
            # free — decode(own) == t - new_err bitwise (the bf16
            # rounding error is Sterbenz-exact in f32) — so the pod hop
            # is a payload *exchange* (ppermute) and only the peer's row
            # pays the one-hot decode.
            swap = [(0, 1), (1, 0)]
            ov = jax.lax.bitcast_convert_type(
                jax.lax.ppermute(wire, "pod", swap), jnp.bfloat16)
            oi = jax.lax.ppermute(idx, "pod", swap)
            own = (gb + ebk - new_eb)[0]
            other = ops.grad_decompress_mean(ov, oi, n, m,
                                             use_pallas=cfg.use_pallas)
            out = (own + other) * 0.5
        else:
            # the pod hop: bf16 vals + u8 idx, N/M of dense bytes
            vals = jax.lax.bitcast_convert_type(
                jax.lax.all_gather(wire, "pod", axis=0, tiled=True),
                jnp.bfloat16)
            idx = jax.lax.all_gather(idx, "pod", axis=0, tiled=True)
            out = ops.grad_decompress_mean(vals, idx, n, m,
                                           use_pallas=cfg.use_pallas)
        return None, (out, new_eb)

    def sync_shard(*args):
        flat_loc, eb, k = args[:-2], args[-2], args[-1]
        if cfg.estimator == "mvue":
            # decorrelate the stochastic draws across pods; intra-pod
            # devices share the key so replicated leaf blocks sample
            # identically (their decoded means must agree bitwise)
            k = jax.random.fold_in(k, jax.lax.axis_index("pod"))
        blocks = [x.reshape(1, -1).astype(jnp.float32)
                  for x, c in zip(flat_loc, comp) if c]
        dense_loc, new_eb = jnp.zeros((0,), jnp.float32), eb
        if n_buckets:
            # zero pad to whole buckets: zero payload and zero residual
            loc = jnp.pad(jnp.concatenate(blocks, axis=1),
                          ((0, 0), (0, n_buckets * bucket - t_loc)))
            ebp = jnp.pad(eb, ((0, 0), (0, n_buckets * bucket - t_loc_pad)))
            as_buckets = lambda x: x.reshape(n_buckets, 1, bucket)
            _, (outs, errs) = jax.lax.scan(
                partial(sync_bucket, k), None,
                (jnp.arange(n_buckets), as_buckets(loc), as_buckets(ebp)))
            dense_loc = outs.reshape(-1)[:t_loc]
            new_eb = errs.reshape(1, -1)[:, :t_loc_pad]
        out, off = [], 0
        for x, c in zip(flat_loc, comp):
            if c:  # unconcat straight back into this device's block
                leaf = dense_loc[off:off + x.size].reshape(x.shape[1:])
                off += x.size
            else:  # dense fp32 pod mean for ragged leaves
                leaf = jax.lax.pmean(x.astype(jnp.float32), "pod")[0]
            out.append(leaf.astype(x.dtype))
        return (*out, new_eb)

    res = jax.shard_map(
        sync_shard, mesh=mesh,
        in_specs=(*(P("pod", *s) for s in flat_s), err_spec, P()),
        out_specs=(*(P(*s) for s in flat_s), err_spec),
        check_vma=False)(*flat_g, err, key)
    return jax.tree_util.tree_unflatten(tdef, list(res[:-1])), res[-1]


def wire_bytes(total: int, ragged: int, cfg: GradCompressConfig) -> int:
    """Per-pod bytes crossing the pod links per step: packed payload
    (bf16 vals + uint8 idx, n per m-group) plus dense fp32 raggeds."""
    groups = total // cfg.m
    return groups * cfg.n * (2 + 1) + ragged * 4
