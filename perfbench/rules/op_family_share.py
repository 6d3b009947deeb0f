"""Share of the device's busy time taken by one family of operations
(self times, so a ``while`` does not count its body twice). A family
none of whose operations ran has nothing to read."""


def read(ctx, family):
    if ctx.reduced is None or not ctx.reduced.busy_s:
        return None
    seconds = ctx.reduced.family_self_s(family)
    return 100.0 * seconds / ctx.reduced.busy_s if seconds else None
