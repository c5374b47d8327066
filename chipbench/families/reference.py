"""Plain float32 pieces that a family's reference is built from.

Written from the model's equations, with no import from the program:
RMSNorm, RoPE, causal GQA attention, top-k routing with per-group
capacity, the head and its loss, and the BDWP N:M training semantics:

    FF : y  = x @ (mask_ff(W) * W)      N of every M along the input axis
    BP : dx = g @ (mask_bp(W) * W)^T    N of every M along the output axis
    WU : dW = x^T @ g                   dense

followed by momentum SGD with weight decay and SR-STE's decay of the
pruned weights (``train_readings``, over the ``Net`` a family gives).
Masks keep the N largest |w| of each group of M, ties to the earlier
entry.

Every matmul goes through ``mm(a, b, q)``: ``q = exact`` is the
reference, run under ``jax.default_matmul_precision("highest")``;
``q = fp8`` rounds both operands to float8 e4m3 with a per-tensor scale,
the control that computes one precision below the configuration's
bfloat16.

Leaves are named by the program's own tree paths (``blocks/attn/q_proj/w``)
so the harness can compare the two leaf by leaf.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

AUX_COEF = 0.01          # weight of the MoE load-balance loss
NEG = -1e30


# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------


def exact(x):
    return x


@jax.custom_vjp
def fp8(x):
    """Round to float8 e4m3 under one scale for the whole tensor; the
    cotangent passes straight through (the backward matmuls round their
    own operands)."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


def mm(a, b, q):
    return jnp.matmul(q(a), q(b), preferred_element_type=jnp.float32)


def nm_keep(w, n: int, m: int, axis: int):
    """True for the n largest |w| of each m consecutive entries along
    ``axis``; of equal entries the earlier one is kept."""
    x = jnp.moveaxis(jnp.abs(w.astype(jnp.float32)), axis, -1)
    g = x.reshape(*x.shape[:-1], x.shape[-1] // m, m)
    pos = jnp.arange(m)
    rank = jnp.zeros(g.shape, jnp.int32)
    for j in range(m):
        gj = g[..., j:j + 1]
        rank = rank + ((gj > g) | ((gj == g) & (j < pos))).astype(jnp.int32)
    return jnp.moveaxis((rank < n).reshape(x.shape), -1, axis)


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def make_linear(n: int, m: int, q):
    """y = x @ W under BDWP: a (K, F) weight's FF mask groups along K,
    its BP mask along F; the weight gradient is dense."""

    @jax.custom_vjp
    def linear(x, w):
        return mm(x, jnp.where(nm_keep(w, n, m, 0), w, 0.0), q)

    def fwd(x, w):
        return linear(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        w_bp = jnp.where(nm_keep(w, n, m, 1), w, 0.0)
        dx = mm(g, w_bp.T, q)
        dw = mm(x.reshape(-1, x.shape[-1]).T, g.reshape(-1, g.shape[-1]), q)
        return dx, dw

    linear.defvjp(fwd, bwd)
    return linear


def _divisor(n: int, cap: int) -> int:
    """Largest divisor of ``n`` not above ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, positions, theta):
    """x (B, S, H, D); rotates the two halves of D."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(qh, kh, vh, q, block: int = 512):
    """Causal GQA softmax attention; query head h reads KV head
    h // (H / KV).  Queries in blocks, each recomputed in the backward
    pass, so no (S, S) score tensor is kept."""
    b, s, h, d = qh.shape
    g = h // kh.shape[2]
    kh = jnp.repeat(kh, g, axis=2)
    vh = jnp.repeat(vh, g, axis=2)
    block = _divisor(s, block)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(qh, i * block, block, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q(qi), q(kh),
                        preferred_element_type=jnp.float32) * d ** -0.5
        qpos = i * block + jnp.arange(block)
        sc = jnp.where(qpos[:, None] >= kpos[None, :], sc, NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(pr), q(vh),
                          preferred_element_type=jnp.float32)

    out = jax.lax.map(one, jnp.arange(s // block))      # (nb, B, blk, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h * d)


def capacity_keep(gate_idx, subject, n_experts: int, group: int, cap: int):
    """Which (token, choice) assignments fit their expert's queue.

    Tokens of a row are taken in groups of ``group`` positions; inside a
    group an expert takes at most ``cap`` assignments, first come first
    served in token order, then choice order.  Tokens with ``subject``
    False are never dropped and take no room."""
    r, s, k = gate_idx.shape
    oh = jax.nn.one_hot(gate_idx, n_experts, dtype=jnp.int32)
    oh = oh * subject[..., None, None].astype(jnp.int32)
    oh = oh.reshape(r, s // group, group * k, n_experts)
    before = jnp.cumsum(oh, axis=2) - oh
    pos = (before * oh).sum(-1).reshape(r, s, k)
    return (pos < cap) | ~subject[..., None]


def routing_group(c, tokens: int) -> int:
    """Tokens per routing group when ``tokens`` are routed together: the
    configured group, or the largest divisor of ``tokens`` below it."""
    g = min(c.group_size, tokens)
    while tokens % g:
        g -= 1
    return g


def logits(p, h, c, q):
    table = p["embed/embed_table"].T if c.tie_embed else p["lm_head/w"]
    out = mm(h, table, q)
    valid = jnp.arange(c.padded_vocab) < c.vocab
    return jnp.where(valid, out, NEG)


def lm_loss(p, h, labels, c, q, chunk: int = 1024):
    """Mean next-token cross-entropy, the sequence taken in chunks."""
    b, s, d = h.shape
    chunk = _divisor(s, chunk)

    @jax.checkpoint
    def one(i):
        hi = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
        li = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        lg = logits(p, hi, c, q)
        gold = jnp.take_along_axis(lg, li[..., None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(lg, axis=-1) - gold).sum()

    return jax.lax.map(one, jnp.arange(s // chunk)).sum() / (b * s)


# ---------------------------------------------------------------------------
# training: three BDWP steps of momentum SGD
# ---------------------------------------------------------------------------


class Net(NamedTuple):
    """What a family gives the training steps: ``init(key, c)`` -> {leaf:
    float32 array} drawn from ``key``; ``hidden(p, tokens, c, lin, q)`` ->
    (final-norm hidden states, summed MoE loss); ``is_pruned(leaf)``:
    whether SR-STE decays the leaf's pruned weights."""

    init: Callable
    hidden: Callable
    is_pruned: Callable


def train_step_fn(net: Net, c, n: int, m: int, lr: float, momentum: float,
                  weight_decay: float, lam: float, q=exact):
    """jit-able (params, momentum, tokens, labels) -> (params, momentum,
    loss, per-leaf gradient norms)."""
    lin = make_linear(n, m, q)

    def total(p, tokens, labels):
        h, aux = net.hidden(p, tokens, c, lin, q)
        ce = lm_loss(p, h, labels, c, q)
        return ce + AUX_COEF * aux, ce

    def step(p, v, tokens, labels):
        (_, ce), g = jax.value_and_grad(total, has_aux=True)(p, tokens, labels)
        gnorm = {k: jnp.linalg.norm(x.ravel()) for k, x in g.items()}
        new_p, new_v = {}, {}
        for k, w in p.items():
            gk = g[k] + weight_decay * w
            if net.is_pruned(k):
                ff = nm_keep(w, n, m, w.ndim - 2)
                gk = gk + lam * jnp.where(ff, 0.0, w)
            new_v[k] = momentum * v[k] + gk
            new_p[k] = w - lr * new_v[k]
        return new_p, new_v, ce, gnorm

    return step


@lru_cache(maxsize=None)
def _jit_init(init, c):
    return jax.jit(partial(init, c=c))


@lru_cache(maxsize=None)
def _jit_train_step(net, c, n, m, lr, momentum, weight_decay, lam, q):
    return jax.jit(train_step_fn(net, c, n, m, lr, momentum, weight_decay, lam, q),
                   donate_argnums=(0, 1))


@lru_cache(maxsize=None)
def _jit_change(init, c):
    make = _jit_init(init, c)
    return jax.jit(lambda p, k: {n_: jnp.linalg.norm((p[n_] - w).ravel())
                                 for n_, w in make(k).items()})


def train_readings(net: Net, c, key, batches, *, n: int, m: int, lr: float,
                   momentum: float, weight_decay: float, lam: float, q=exact,
                   drop_half: bool = False):
    """Follow the first ``len(batches)`` steps of ``net`` at sizes ``c``
    from the seed's weights.  Returns {"loss": [...], "grad": {leaf: |g0|},
    "change": {leaf: |w_T - w_0|}} as host numbers.  ``drop_half`` leaves
    out the second half of every batch (a planted fault, read against the
    whole)."""
    with jax.default_matmul_precision("highest"):
        make = _jit_init(net.init, c)
        p = make(key)
        v = jax.tree.map(jnp.zeros_like, p)
        step = _jit_train_step(net, c, n, m, lr, momentum, weight_decay, lam, q)
        losses, grad = [], None
        for tokens, labels in batches:
            if drop_half:   # half of the rows, or of the one row's tokens
                half = (slice(len(tokens) // 2),) if len(tokens) > 1 else \
                    (slice(None), slice(tokens.shape[1] // 2))
                tokens, labels = tokens[half], labels[half]
            p, v, ce, gnorm = step(p, v, jnp.asarray(tokens), jnp.asarray(labels))
            losses.append(float(ce))
            if grad is None:
                grad = {k: float(x) for k, x in gnorm.items()}
        del v
        change = {k: float(x) for k, x in _jit_change(net.init, c)(p, key).items()}
    return {"loss": losses, "grad": grad, "change": change}
