"""Device time per train step of the forward products of the N:M
linears (scope ``ff`` in ``core/operand``; the remat recompute of the
forward counts here too)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "ff")
