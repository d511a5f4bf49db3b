"""``memory_stats()["peak_bytes_in_use"]`` after the window, on the
fullest chip."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
