"""Device time per train step of the embedding lookup, the final norm,
the head's logits and the cross-entropy, with their gradients (scope
``embed_head``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "embed_head")
