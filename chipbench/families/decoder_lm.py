"""Family ``decoder_lm``: the decoder LMs of ``transformer_lm`` with GQA
attention (optional qk-norm), a SwiGLU MLP or top-k routed SwiGLU experts
in every layer, and a tied or untied head.  Sizes come from the file's
Hugging Face keys (``model``); the reference draws its own weights with
the program's key schedule, so both start from the same weights.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.families import reference as RF
from chipbench.families.flops import pruned
from chipbench.families.reference import (attention, capacity_keep, mm,
                                          rmsnorm, rope, routing_group)

@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes of a configuration file, under the benchmark's own names.
    The reference and the operation counts read only this."""

    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    d_ff: int = 0
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    tie_embed: bool = True
    qk_norm: bool = False
    rope_theta: float = 1e4
    rms_eps: float = 1e-6
    pad_vocab_to: int = 256
    capacity_factor: float = 1.25
    group_size: int = 512

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.pad_vocab_to) * self.pad_vocab_to

    @property
    def moe(self) -> bool:
        return self.n_experts > 0


def model(cfg: dict) -> Model:
    """Configuration file (Hugging Face key names) -> Model."""
    heads = cfg["num_attention_heads"]
    experts = cfg.get("num_local_experts", 0)
    return Model(
        d_model=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=heads, n_kv=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", cfg["hidden_size"] // heads),
        vocab=cfg["vocab_size"],
        d_ff=0 if experts else cfg["intermediate_size"],
        n_experts=experts, top_k=cfg.get("num_experts_per_tok", 0),
        d_expert=cfg["intermediate_size"] if experts else 0,
        tie_embed=cfg["tie_word_embeddings"],
        qk_norm=cfg.get("qk_norm", False), rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"],
        pad_vocab_to=cfg["program"]["pad_vocab_to"],
        capacity_factor=cfg["program"].get("moe_capacity_factor", 1.25),
        group_size=cfg["program"].get("moe_group_size", 512))


def program_config(conf: dict):
    """The program's LMConfig for a configuration file: the architecture's
    published config with the file's cuts; every other size must agree."""
    from repro.configs import get_arch

    base = getattr(get_arch(conf["arch"]), conf.get("preset", "full"))
    cfg = dataclasses.replace(base, n_layers=conf["num_hidden_layers"],
                              vocab=conf["vocab_size"])
    want = model(conf)
    have = {"d_model": cfg.d_model, "n_heads": cfg.n_heads, "n_kv": cfg.n_kv,
            "head_dim": cfg.head_dim, "tie_embed": cfg.tie_embed,
            "qk_norm": cfg.qk_norm, "rope_theta": cfg.rope_theta,
            "pad_vocab_to": cfg.pad_vocab_to, "d_ff": cfg.d_ff}
    if cfg.moe is not None:
        have.update(n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
                    d_expert=cfg.moe.d_expert,
                    capacity_factor=cfg.moe.capacity_factor,
                    group_size=cfg.moe.group_size)
    bad = {k: (v, getattr(want, k)) for k, v in have.items()
           if v != getattr(want, k)}
    if bad:
        raise ValueError(f"{conf['name']}: program and file disagree "
                         f"(program, file): {bad}")
    return cfg


# ---------------------------------------------------------------------------
# reference
# ---------------------------------------------------------------------------


def init(key, c) -> dict:
    """Flat {program tree path: float32 array}, drawn from ``key``."""
    d = c.d_model
    k_embed, k_blocks, _, k_out = jax.random.split(key, 4)
    nrm = jax.random.normal
    p = {"embed/embed_table":
         nrm(k_embed, (c.padded_vocab, d), jnp.float32) * d ** -0.5}
    blocks = jax.vmap(partial(_block_init, c=c))(
        jax.random.split(k_blocks, c.n_layers))
    p.update({"blocks/" + k: v for k, v in blocks.items()})
    p["final_norm/norm_scale"] = jnp.ones((d,), jnp.float32)
    if not c.tie_embed:
        p["lm_head/w"] = nrm(k_out, (d, c.padded_vocab), jnp.float32) * d ** -0.5
    return p


def _block_init(key, c) -> dict:
    d, h, kv, hd = c.d_model, c.n_heads, c.n_kv, c.head_dim
    nrm = jax.random.normal
    ks = jax.random.split(key, 6)
    a = jax.random.split(ks[0], 8)
    p = {"ln1/norm_scale": jnp.ones((d,), jnp.float32),
         "ln2/norm_scale": jnp.ones((d,), jnp.float32),
         "attn/q_proj/w": nrm(a[0], (d, h * hd), jnp.float32) * d ** -0.5,
         "attn/k_proj/w": nrm(a[1], (d, kv * hd), jnp.float32) * d ** -0.5,
         "attn/v_proj/w": nrm(a[2], (d, kv * hd), jnp.float32) * d ** -0.5,
         "attn/o_proj/w": nrm(a[3], (h * hd, d), jnp.float32) * (h * hd) ** -0.5}
    if c.qk_norm:
        p["attn/q_norm/norm_scale"] = jnp.ones((hd,), jnp.float32)
        p["attn/k_norm/norm_scale"] = jnp.ones((hd,), jnp.float32)
    if c.moe:
        e, f = c.n_experts, c.d_expert
        m = jax.random.split(ks[2], 8)
        p["moe/router/w"] = nrm(m[0], (d, e), jnp.float32) * d ** -0.5
        p["moe/w_gate"] = nrm(m[1], (e, d, f), jnp.float32) * d ** -0.5
        p["moe/w_up"] = nrm(m[2], (e, d, f), jnp.float32) * d ** -0.5
        p["moe/w_down"] = nrm(m[3], (e, f, d), jnp.float32) * f ** -0.5
    else:
        f = c.d_ff
        g, u, dn = jax.random.split(ks[3], 3)
        p["ffn/w_gate/w"] = nrm(g, (d, f), jnp.float32) * d ** -0.5
        p["ffn/w_up/w"] = nrm(u, (d, f), jnp.float32) * d ** -0.5
        p["ffn/w_down/w"] = nrm(dn, (f, d), jnp.float32) * f ** -0.5
    return p


PRUNED = ("attn/q_proj/w", "attn/k_proj/w", "attn/v_proj/w", "attn/o_proj/w",
          "moe/w_gate", "moe/w_up", "moe/w_down",
          "ffn/w_gate/w", "ffn/w_up/w", "ffn/w_down/w")


def is_pruned(name: str) -> bool:
    return any(name.endswith(s) for s in PRUNED)


def moe(p, x, c, lin, q, subject, group):
    """Top-k routed SwiGLU experts; returns (y, load-balance loss).
    ``group``: tokens per routing group, counted along the rows of ``x``
    flattened (training) or within each row (a prefilled prompt)."""
    r, s, d = x.shape
    e, k = c.n_experts, c.top_k
    logits = mm(x, p["moe/router/w"], q)
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    cap = min(int(max(k, round(group * c.capacity_factor * k / e))), group)
    if s % group:   # groups run across rows: route the rows as one
        keep = capacity_keep(idx.reshape(1, r * s, k), subject.reshape(1, -1),
                             e, group, cap).reshape(r, s, k)
    else:
        keep = capacity_keep(idx, subject, e, group, cap)
    gates = (jax.nn.one_hot(idx, e) * (top * keep)[..., None]).sum(2)  # (r,s,e)

    def expert(acc, xs):
        wg, wu, wd, ge = xs
        hdn = jax.nn.silu(lin(x, wg)) * lin(x, wu)
        return acc + lin(hdn, wd) * ge[..., None], None

    expert = jax.checkpoint(expert)
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (p["moe/w_gate"], p["moe/w_up"], p["moe/w_down"],
                         jnp.moveaxis(gates, -1, 0)))
    counts = jax.lax.stop_gradient(
        (jax.nn.one_hot(idx, e) * keep[..., None]).sum((0, 1, 2)))
    aux = e * jnp.sum(probs.mean((0, 1)) * counts / jnp.maximum(counts.sum(), 1.0))
    return y, aux


def block(p, x, positions, subject, group, c, lin, q):
    h = rmsnorm(x, p["ln1/norm_scale"], c.rms_eps)
    b, s, _ = x.shape
    qh = lin(h, p["attn/q_proj/w"]).reshape(b, s, c.n_heads, c.head_dim)
    kh = lin(h, p["attn/k_proj/w"]).reshape(b, s, c.n_kv, c.head_dim)
    vh = lin(h, p["attn/v_proj/w"]).reshape(b, s, c.n_kv, c.head_dim)
    if c.qk_norm:
        qh = rmsnorm(qh, p["attn/q_norm/norm_scale"], c.rms_eps)
        kh = rmsnorm(kh, p["attn/k_norm/norm_scale"], c.rms_eps)
    qh = rope(qh, positions, c.rope_theta)
    kh = rope(kh, positions, c.rope_theta)
    x = x + lin(attention(qh, kh, vh, q), p["attn/o_proj/w"])
    h = rmsnorm(x, p["ln2/norm_scale"], c.rms_eps)
    if c.moe:
        y, aux = moe(p, h, c, lin, q, subject, group)
    else:
        y = lin(jax.nn.silu(lin(h, p["ffn/w_gate/w"])) * lin(h, p["ffn/w_up/w"]),
                p["ffn/w_down/w"])
        aux = jnp.zeros((), jnp.float32)
    return x + y, aux


def hidden(p, tokens, c, lin, q, subject=None, group=None):
    """Final-norm hidden states (B, S, d) and the summed MoE loss.
    Without ``subject``/``group`` every token is routed under capacity,
    all rows together (a training batch)."""
    b, s = tokens.shape
    x = p["embed/embed_table"][tokens].astype(jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    if subject is None:
        subject = jnp.ones((b, s), bool)
    if group is None:
        group = routing_group(c, b * s)
    layers = {k[len("blocks/"):]: v for k, v in p.items()
              if k.startswith("blocks/")}

    @jax.checkpoint
    def body(x, lp):
        lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
        return block(lp, x, positions, subject, group, c, lin, q)

    x, aux = jax.lax.scan(body, x, layers)
    return rmsnorm(x, p["final_norm/norm_scale"], c.rms_eps), aux.sum()


NET = RF.Net(init, hidden, is_pruned)


def train_readings(conf: dict, key, batches, *, control: bool = False, **opt):
    """The reference's readings of the first steps (``RF.train_readings``;
    ``opt`` its optimizer and sparsity keywords), in float32 or, with
    ``control``, in float8 in the program's place."""
    return RF.train_readings(NET, model(conf), key, batches,
                             q=RF.fp8 if control else RF.exact, **opt)


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------


def linears(c) -> list:
    """[(tree path, K, F, uses per token)] of one model, per layer for
    the ``blocks/`` entries (which repeat ``n_layers`` times)."""
    d, h, kv, hd = c.d_model, c.n_heads, c.n_kv, c.head_dim
    out = [("blocks/attn/q_proj/w", d, h * hd, 1),
           ("blocks/attn/k_proj/w", d, kv * hd, 1),
           ("blocks/attn/v_proj/w", d, kv * hd, 1),
           ("blocks/attn/o_proj/w", h * hd, d, 1)]
    if c.moe:
        e, f, k = c.n_experts, c.d_expert, c.top_k
        out += [("blocks/moe/router/w", d, e, 1),
                ("blocks/moe/w_gate", d, f, k),
                ("blocks/moe/w_up", d, f, k),
                ("blocks/moe/w_down", f, d, k)]
    else:
        f = c.d_ff
        out += [("blocks/ffn/w_gate/w", d, f, 1),
                ("blocks/ffn/w_up/w", d, f, 1),
                ("blocks/ffn/w_down/w", f, d, 1)]
    return out


def _head(c) -> tuple:
    name = "embed/embed_table" if c.tie_embed else "lm_head/w"
    return name, c.d_model, c.vocab


def train_flops_per_token(conf: dict, seq: int, n: int, m: int) -> dict:
    """{"sparse": BDWP count, "dense": the same work with no weight
    pruned} per token of a training step at sequence length ``seq``."""
    c = model(conf)
    sparse = dense = 0.0
    for name, k, f, uses in linears(c):
        full = 2.0 * k * f * uses * c.n_layers
        dense += 3 * full
        sparse += (2 * full * n / m + full) if pruned(name, (k, f), m) else 3 * full
    _, d, v = _head(c)
    dense += 6.0 * d * v
    sparse += 6.0 * d * v
    attn = 6.0 * seq * c.n_heads * c.head_dim * c.n_layers  # causal: S/2 keys
    return {"sparse": sparse + attn, "dense": dense + attn}
