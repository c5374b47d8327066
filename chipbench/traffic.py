"""One generator for every traffic file: training batches drawn from the
run's ``--seed``.

A training mix gives ``batch`` rows of ``seq`` tokens a step; tokens
follow a Zipf law over the configuration's vocabulary with every
``copy_period``-th token repeating the one ``copy_period`` back (the
repo's synthetic stream, ``repro.data.synthetic.token_batch``, copied).
Step ``t`` of seed ``s`` is always the same batch, and no two steps share
a row.
"""

from __future__ import annotations

import numpy as np


def key_seed(seed: int) -> int:
    """The run seed folded into the 32 bits a JAX key holds."""
    return int(seed) % (1 << 32)


def train_batch(mix: dict, vocab: int, seed: int, step: int):
    """(tokens, labels), each (batch, seq) int32: labels are the next tokens."""
    rng = np.random.default_rng([key_seed(seed), 0x7EA1, step])
    b, s, period = mix["batch"], mix["seq"], mix["copy_period"]
    probs = np.arange(1, vocab + 1, dtype=np.float64) ** -mix["zipf_a"]
    probs /= probs.sum()
    toks = rng.choice(vocab, size=(b, s + 1), p=probs)
    for i in range(period, s + 1, period):
        toks[:, i] = toks[:, i - period]
    toks = toks.astype(np.int32)
    return toks[:, :-1], toks[:, 1:]
