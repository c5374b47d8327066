"""Device time per train step of attention outside its q/k/v/o
linears: rope, scores, softmax, the weighted sum and their gradients
(scope ``attention`` around ``attn_apply``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "attention")
