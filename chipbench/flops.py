"""Operations and bytes the work needs, from the configuration's shapes.

A matmul is counted at the work the algorithm needs, whatever runs it:

* an N:M-pruned linear with weight K x F over T tokens needs, under BDWP,
  2·T·K·F·N/M forward, the same backward to the input, and 2·T·K·F for
  the (dense) weight gradient; an unpruned one 6·T·K·F;
* MoE experts count only the top-k that each token is routed to;
* causal attention counts the lower triangle, once forward and twice
  backward;
* recomputation under remat is not counted.

Which weights are pruned is the program's rule, kept here as a copy:
weights consumed through the N:M linear (``.../w`` leaves and the MoE
expert stacks), unless their name holds one of the excluded fragments
or is the directly-consumed ``lm_head``, and only where both grouped axes
divide into groups of M.  ``tests/test_flops.py`` holds the copy against
the program's own list.
"""

from __future__ import annotations

EXCLUDED = ("embed", "router", "norm", "frontend", "bias", "head0")
DIRECT = ("lm_head",)
BARE = ("w_gate", "w_up", "w_down")


def pruned(name: str, lshape, m: int) -> bool:
    """The program's rule: is this leaf (tree path, per-layer shape) an
    N:M site under BDWP with groups of ``m``?"""
    if not (name.endswith("/w") or name.rsplit("/", 1)[-1] in BARE):
        return False
    if len(lshape) < 2 or any(f in name for f in EXCLUDED + DIRECT):
        return False
    k, f = lshape[-2], lshape[-1]
    return all(a % m == 0 and a >= 2 * m for a in (k, f))


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


def train_flops_per_token(c, seq: int, n: int, m: int) -> dict:
    """{"sparse": BDWP count, "dense": the same work with no weight
    pruned} per token of a training step at sequence length ``seq``."""
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
