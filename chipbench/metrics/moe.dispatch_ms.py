"""Device time per train step of MoE routing outside the experts:
router, softmax and top-k, slot assignment, dispatch and combine
gathers, the aux loss and their gradients (scope ``moe_dispatch``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "moe_dispatch")
