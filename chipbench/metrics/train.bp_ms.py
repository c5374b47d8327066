"""Device time per train step of the data gradients ``dx`` of the N:M
linears (scope ``bp`` in ``core/operand``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "bp")
