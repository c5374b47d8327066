"""Share of the train step's op self time that no scope names: the
part of the step the per-layer metrics cannot place."""

from chipbench import layer_time as LT


def read(ctx):
    per = LT.layer_ms(ctx)
    if not per or per["_op"] <= 0:
        return None
    return 100.0 * per.get("unscoped", 0.0) / per["_op"]
