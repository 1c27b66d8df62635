"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when
the window closed and before the reference ran, in GB."""


def read(how, ctx):
    peak = ctx.obs["counters"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
