"""Share of the traced training window in which no operation ran on the
device: host work between steps of ``trainer.fit`` (feed, sync on the
loss).  Nothing to read in a trace without a device plane."""


def read(ctx):
    if ctx["window_s"] <= 0 or not ctx["trace"].devices:
        return None
    return 100.0 * (1.0 - ctx["busy_s"] / ctx["window_s"])
