"""Share of the train step's op self time in fusions whose ops come from
more than one scope.  The per-layer split gives such a fusion wholly to
one layer by rule (its own name's, else its root's), so this is the part
of that split a rule, and not a scope, decides."""

from chipbench import layer_time as LT


def read(ctx):
    per = LT.layer_ms(ctx)
    if not per or per["_op"] <= 0:
        return None
    return 100.0 * per["_mixed"] / per["_op"]
