"""1 - busy / window, between the first and the last device operation
of the traced window, averaged over the chips."""


def read(ctx):
    if ctx.reduced is None:
        return None
    return 100.0 * ctx.reduced.idle_share
