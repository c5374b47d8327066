"""Unified decoder-LM: dense / GQA / MLA / SWA / MoE / SSM / hybrid.

One scan-over-layers model definition covers qwen3, qwen2.5, glm4,
gemma3 (5:1 local:global), granite-moe, deepseek-v2-lite (MLA + MoE +
dense first layer), mamba2 (attention-free), hymba (parallel attn+SSM
heads) and internvl2 (LM backbone + stubbed vision prefix).

Params are stacked along a leading "layer" axis and scanned, so compile
time is O(1) in depth; heterogeneous layer patterns (gemma3's local vs
global) are handled with per-layer flags + lax.cond inside the scan.
Every projection routes through BDWP (core/bdwp).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bdwp
from repro.core import scopes as SC
from repro.core.sparsity import DENSE, SparsityConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.sharding.rules import BATCH, SEQ, act


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int = 0
    n_kv: int = 0
    head_dim: int = 128
    d_ff: int = 0
    rope_theta: float = 1e4
    qk_norm: bool = False
    qkv_bias: bool = False
    # layer pattern, cycled over depth: "attn" | "swa" | "mamba" | "hybrid"
    pattern: tuple = ("attn",)
    window: Optional[int] = None
    # MoE
    moe: Optional[M.MoEConfig] = None
    first_dense_ff: Optional[int] = None  # deepseek: dense FFN in layer 0
    # MLA
    kv_lora: Optional[int] = None
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: Optional[int] = None
    # SSM
    ssm_state: int = 128
    ssm_head_dim: int = 64
    ssm_chunk: int = 128
    tie_embed: bool = True
    remat: bool = True
    # vocab-parallel embedding/LM-head tables are padded up to a multiple
    # of this (Megatron/MaxText convention) so the "vocab" axis divides the
    # TP mesh axis evenly; padded logit columns are masked to -inf.
    pad_vocab_to: int = 256

    @property
    def padded_vocab(self) -> int:
        return -(-self.vocab // self.pad_vocab_to) * self.pad_vocab_to

    def attn_cfg(self) -> A.AttnConfig:
        return A.AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            qk_norm=self.qk_norm, qkv_bias=self.qkv_bias, window=self.window,
            kv_lora=self.kv_lora, qk_nope_dim=self.qk_nope_dim,
            qk_rope_dim=self.qk_rope_dim, v_head_dim=self.v_head_dim,
        )

    def ssm_cfg(self) -> S.SSMConfig:
        return S.SSMConfig(d_model=self.d_model, d_state=self.ssm_state,
                           head_dim=self.ssm_head_dim, chunk=self.ssm_chunk)

    def layer_kinds(self):
        pat = list(self.pattern)
        kinds = [pat[i % len(pat)] for i in range(self.n_layers)]
        return kinds

    @property
    def has_attn(self) -> bool:
        return any(k in ("attn", "swa", "hybrid") for k in self.layer_kinds())

    @property
    def has_ssm(self) -> bool:
        return any(k in ("mamba", "hybrid") for k in self.layer_kinds())

    @property
    def uses_scan_prelude(self) -> bool:
        return self.first_dense_ff is not None

    def n_params(self) -> int:
        """Total parameter count (for MODEL_FLOPS accounting)."""
        import math

        p, _ = init(jax.random.PRNGKey(0), self, abstract=True)
        return sum(math.prod(x.shape) for x in jax.tree.leaves(p))

    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k of routed experts)."""
        total = self.n_params()
        if self.moe is None:
            return total
        e, k = self.moe.n_experts, self.moe.top_k
        expert_p = 3 * self.d_model * self.moe.d_expert
        n_moe_layers = self.n_layers - (1 if self.uses_scan_prelude else 0)
        inactive = n_moe_layers * (e - k) * expert_p
        return total - inactive


# ---------------------------------------------------------------------------
# FFN (dense SwiGLU)
# ---------------------------------------------------------------------------


def ffn_init(key, d: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    p, s = {}, {}
    p["w_gate"], s["w_gate"] = L.dense_init(k1, d, d_ff, axes=("embed", "mlp"))
    p["w_up"], s["w_up"] = L.dense_init(k2, d, d_ff, axes=("embed", "mlp"))
    p["w_down"], s["w_down"] = L.dense_init(k3, d_ff, d, axes=("mlp", "embed"))
    return p, s


def ffn_apply(p, x, sp_cfg):
    h = L.swiglu(L.dense_apply(p["w_gate"], x, "mlp/w_gate", sp_cfg),
                 L.dense_apply(p["w_up"], x, "mlp/w_up", sp_cfg))
    h = act(h, BATCH, None, "model")  # TP: FFN hidden sharded over model
    return L.dense_apply(p["w_down"], h.astype(x.dtype), "mlp/w_down", sp_cfg)


# ---------------------------------------------------------------------------
# One transformer block (scanned)
# ---------------------------------------------------------------------------


def _block_init(key, cfg: LMConfig):
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.rmsnorm_init(cfg.d_model)
    p["ln2"], s["ln2"] = L.rmsnorm_init(cfg.d_model)
    kinds = set(cfg.layer_kinds())
    if kinds & {"attn", "swa", "hybrid"}:
        p["attn"], s["attn"] = A.attn_init(ks[0], cfg.attn_cfg())
    if kinds & {"mamba", "hybrid"}:
        p["ssm"], s["ssm"] = S.ssm_init(ks[1], cfg.ssm_cfg())
    if cfg.moe is not None:
        p["moe"], s["moe"] = M.moe_init(ks[2], cfg.d_model, cfg.moe)
    elif cfg.d_ff:
        p["ffn"], s["ffn"] = ffn_init(ks[3], cfg.d_model, cfg.d_ff)
    return p, s


def _block_apply(p, x, cfg: LMConfig, sp_cfg, *, positions, is_global,
                 cache=None, decode=False, per_slot=False):
    """Returns (x, new_cache, aux_loss)."""
    kinds = cfg.layer_kinds()
    kind0 = kinds[0] if len(set(kinds)) == 1 else None
    x = act(x, BATCH, SEQ, None)  # anchor: DP batch (+ seq-parallel)
    h = L.rmsnorm_apply(p["ln1"], x)
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    acfg = cfg.attn_cfg()

    if kind0 == "mamba":
        mix, nc = S.ssm_apply(p["ssm"], h, cfg.ssm_cfg(), sp_cfg,
                              cache=cache, decode=decode)
        if nc is not None:
            new_cache = nc
    elif kind0 == "hybrid":
        a_cache = {k: v for k, v in cache.items() if k in ("k", "v", "pos")} \
            if cache is not None else None
        s_cache = {k: v for k, v in cache.items() if k in ("state", "conv")} \
            if cache is not None else None
        with jax.named_scope(SC.ATTENTION):
            a_out, a_nc = A.attn_apply(p["attn"], h, acfg, sp_cfg,
                                       positions=positions, cache=a_cache,
                                       layer_window=cfg.window, decode=decode,
                                       per_slot=per_slot)
        s_out, s_nc = S.ssm_apply(p["ssm"], h, cfg.ssm_cfg(), sp_cfg,
                                  cache=s_cache, decode=decode)
        mix = 0.5 * (a_out + s_out)  # hymba: parallel heads, mean-combined
        if a_nc is not None:
            new_cache.update(a_nc)
        if s_nc is not None:
            new_cache.update(s_nc)
    else:
        # attn / swa (possibly mixed per-layer, e.g. gemma3 5:1)
        if "swa" in kinds and "attn" in kinds:
            def global_branch(h_):
                return A.attn_apply(p["attn"], h_, acfg, sp_cfg,
                                    positions=positions, cache=cache,
                                    layer_window=None, decode=decode,
                                    per_slot=per_slot)

            def local_branch(h_):
                return A.attn_apply(p["attn"], h_, acfg, sp_cfg,
                                    positions=positions, cache=cache,
                                    layer_window=cfg.window, decode=decode,
                                    per_slot=per_slot)

            with jax.named_scope(SC.ATTENTION):
                mix, nc = jax.lax.cond(is_global, global_branch,
                                       local_branch, h)
        else:
            window = cfg.window if kinds[0] == "swa" else None
            with jax.named_scope(SC.ATTENTION):
                mix, nc = A.attn_apply(p["attn"], h, acfg, sp_cfg,
                                       positions=positions, cache=cache,
                                       layer_window=window, decode=decode,
                                       per_slot=per_slot)
        if nc is not None:
            new_cache = nc
    x = x + mix

    h2 = L.rmsnorm_apply(p["ln2"], x)
    if cfg.moe is not None:
        y, aux = M.moe_apply(p["moe"], h2, cfg.moe, sp_cfg)
    elif "ffn" in p:
        y = ffn_apply(p["ffn"], h2, sp_cfg)
    else:
        y = jnp.zeros_like(h2)
    x = x + y
    return x, (new_cache if new_cache else None), aux


# ---------------------------------------------------------------------------
# Full model: init / apply / prefill / decode
# ---------------------------------------------------------------------------


def _is_axes(x):
    return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)


def init(key, cfg: LMConfig, abstract: bool = False):
    """Returns (params, specs).  abstract=True gives ShapeDtypeStruct leaves
    with zero device allocation (used by the dry-run)."""
    spec_box = {}

    def build(key):
        k_embed, k_blocks, k_pre, k_out = jax.random.split(key, 4)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embed_init(
            k_embed, cfg.padded_vocab, cfg.d_model)
        n_scan = cfg.n_layers - (1 if cfg.uses_scan_prelude else 0)
        bkeys = jax.random.split(k_blocks, n_scan)
        params["blocks"] = jax.vmap(lambda k: _block_init(k, cfg)[0])(bkeys)
        bspec = _block_spec_of(cfg)
        specs["blocks"] = jax.tree.map(
            lambda ax: ("layer",) + tuple(ax), bspec, is_leaf=_is_axes)
        if cfg.uses_scan_prelude:
            pre_p, pre_s = {}, {}
            pre_p["ln1"], pre_s["ln1"] = L.rmsnorm_init(cfg.d_model)
            pre_p["ln2"], pre_s["ln2"] = L.rmsnorm_init(cfg.d_model)
            pre_p["attn"], pre_s["attn"] = A.attn_init(k_pre, cfg.attn_cfg())
            pre_p["ffn"], pre_s["ffn"] = ffn_init(k_out, cfg.d_model,
                                                  cfg.first_dense_ff)
            params["prelude"], specs["prelude"] = pre_p, pre_s
        params["final_norm"], specs["final_norm"] = L.rmsnorm_init(cfg.d_model)
        if not cfg.tie_embed:
            params["lm_head"], specs["lm_head"] = L.dense_init(
                k_out, cfg.d_model, cfg.padded_vocab, axes=("embed", "vocab"))
        spec_box["specs"] = specs
        return params

    if abstract:
        shapes = jax.eval_shape(build, key)
        return shapes, spec_box["specs"]
    params = build(key)
    return params, spec_box["specs"]


def _block_spec_of(cfg: LMConfig):
    """Spec tree of one block, computed without allocation (eval_shape +
    side-channel; specs are plain python tuples independent of key)."""
    box = {}

    def f(k):
        p, s = _block_init(k, cfg)
        box["s"] = s
        return p

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return box["s"]


def _layer_flags(cfg: LMConfig):
    kinds = cfg.layer_kinds()
    if cfg.uses_scan_prelude:
        kinds = kinds[1:]
    return jnp.asarray([k == "attn" for k in kinds], jnp.bool_)


def forward(params, tokens, cfg: LMConfig, sp_cfg: SparsityConfig = DENSE, *,
            prefix_embeds=None, cache=None, decode=False, positions=None,
            per_slot=False):
    """Shared trunk: returns (hidden (B,S,d), new_cache, aux_loss).

    prefix_embeds: (B, S_img, d) stub-frontend embeddings prepended to the
    token embeddings (internvl2 / whisper-style modality prefix).

    per_slot (decode only): treat every batch row as an independent
    request slot — cache writes/masks are indexed by the per-row
    ``positions`` instead of the shared ``cache["pos"]`` cursor (the
    serve engine's continuous-batching mode).
    """
    with jax.named_scope(SC.EMBED_HEAD):
        x = L.embed_apply(params["embed"], tokens)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        x = act(x, BATCH, SEQ, None)
    b, s_tot = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s_tot), (b, s_tot))
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.uses_scan_prelude:
        pre = params["prelude"]
        pc = cache["prelude"] if cache is not None else None
        with jax.named_scope(SC.BLOCKS):
            h = L.rmsnorm_apply(pre["ln1"], x)
            with jax.named_scope(SC.ATTENTION):
                mix, pre_nc = A.attn_apply(pre["attn"], h, cfg.attn_cfg(),
                                           sp_cfg, positions=positions,
                                           cache=pc, decode=decode,
                                           per_slot=per_slot)
            x = x + mix
            x = x + ffn_apply(pre["ffn"], L.rmsnorm_apply(pre["ln2"], x),
                              sp_cfg)
    else:
        pre_nc = None

    flags = _layer_flags(cfg)

    def body(carry, xs):
        xh, aux = carry
        bp, flag, layer_cache = xs
        fn = partial(_block_apply, cfg=cfg, sp_cfg=sp_cfg, positions=positions,
                     decode=decode, per_slot=per_slot)
        if cfg.remat and not decode:
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.nothing_saveable,
                static_argnums=())
        xh, nc, a = fn(bp, xh, is_global=flag, cache=layer_cache)
        return (xh, aux + a), nc

    layer_caches = cache["layers"] if cache is not None else None
    with jax.named_scope(SC.BLOCKS):
        if layer_caches is None:
            (x, aux_total), _ = jax.lax.scan(
                lambda c, xs: _strip_cache(body(c, (*xs, None))),
                (x, aux_total), (params["blocks"], flags))
            new_cache = None
        else:
            (x, aux_total), new_layer_caches = jax.lax.scan(
                body, (x, aux_total), (params["blocks"], flags, layer_caches))
            new_cache = {"layers": new_layer_caches}
            if pre_nc is not None:
                new_cache["prelude"] = pre_nc

    with jax.named_scope(SC.EMBED_HEAD):
        x = act(x, BATCH, SEQ, None)
        x = L.rmsnorm_apply(params["final_norm"], x)
    return x, new_cache, aux_total


def _strip_cache(res):
    carry, _ = res
    return carry, None


def logits_from_hidden(params, hidden, cfg: LMConfig):
    table = params["embed"]["embed_table"] if cfg.tie_embed else params["lm_head"]["w"].T
    with jax.named_scope(SC.EMBED_HEAD):
        logits = jnp.matmul(hidden, table.T.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
        if cfg.padded_vocab != cfg.vocab:  # mask padded columns (static)
            valid = jnp.arange(cfg.padded_vocab) < cfg.vocab
            logits = jnp.where(valid, logits, -1e30)
    return logits


def lm_loss(params, hidden, labels, cfg: LMConfig, *, chunk: int = 1024,
            mask=None):
    """Chunked cross-entropy: never materializes (B, S, V) at once."""
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def step(acc, xs):
        h, l, mk = xs
        logits = logits_from_hidden(params, h, cfg)  # (B, c, V) fp32
        logits = act(logits, BATCH, None, "model")  # vocab-TP logits
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, l[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mk
        return (acc[0] + nll.sum(), acc[1] + mk.sum()), None

    with jax.named_scope(SC.EMBED_HEAD):
        hs = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)
        ls = labels.reshape(b, nc, chunk).swapaxes(0, 1)
        if mask is None:
            ms = jnp.ones((nc, b, chunk), jnp.float32)
        else:
            ms = mask.reshape(b, nc, chunk).swapaxes(0, 1).astype(jnp.float32)
        (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                                     (hs, ls, ms))
        return tot / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Cache init (stacked across scanned layers)
# ---------------------------------------------------------------------------


def init_lm_cache(cfg: LMConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    kinds = cfg.layer_kinds()
    n_scan = cfg.n_layers - (1 if cfg.uses_scan_prelude else 0)
    scan_kinds = kinds[1:] if cfg.uses_scan_prelude else kinds

    def one_layer(kind):
        c = {}
        if kind in ("attn", "swa", "hybrid"):
            c.update(A.init_cache(cfg.attn_cfg(), batch, max_len, dtype))
        if kind in ("mamba", "hybrid"):
            c.update(S.init_ssm_cache(cfg.ssm_cfg(), batch))
        return c

    per_layer = [one_layer(k) for k in scan_kinds]
    # all scanned layers share a structure -> stack
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_layer)
    cache = {"layers": stacked}
    if cfg.uses_scan_prelude:
        cache["prelude"] = A.init_cache(cfg.attn_cfg(), batch, max_len, dtype)
    return cache
