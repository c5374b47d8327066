"""Peak device memory in use by the end of the window, as the runtime
counts it (``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(ctx):
    peak = ctx.get("peak_bytes")
    return peak / 2 ** 30 if peak else None
