"""Mean per step of the host's lag in seeing a step end: from the end of
the step program's run on the device to the end of the ``fit.sync`` span
(``block_until_ready`` and ``float`` of the loss) that waited for it.
The mean, because the sum is what the window loses."""

from chipbench import layer_time as LT


def read(ctx):
    return LT.read_sync_lag_ms(ctx)
