"""How a family counts the operations the work needs from its shapes
(its ``train_flops_per_token``), and the program's pruning rule.

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
