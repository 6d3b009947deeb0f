"""Peak device memory of the fullest chip after the window, GiB."""


def read(ctx):
    return ctx.memory_peak_bytes / 2**30 if ctx.memory_peak_bytes else None
