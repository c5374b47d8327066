"""Device time per train step of the dense weight gradients ``dw`` of
the N:M linears (scope ``wu`` in ``core/operand``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "wu")
