"""Device time per train step of the layer scan outside the scoped
layers: norms, residuals, activations and the scan's per-layer stacking
of saved values (scope ``blocks``)."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_layer(ctx, "blocks")
