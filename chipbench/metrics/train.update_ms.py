"""Device time per train step of the weight update (scope ``update`` in
``optim/sgd``): momentum, decay, SR-STE, the master update and the FF/BP
operands pre-generated from it.  XLA fuses these into one set of
fusions, so the update is read whole."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "update")
