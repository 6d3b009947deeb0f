"""Roofline share of the matrix products (convolutions and dense
layers): the least time the chip could take for the traced window's
work, from ``harness/flops.py`` and ``harness/peaks.py``, over the time
the trace shows for that family of operations."""

from perfbench.harness import flops


def read(ctx, family):
    if ctx.reduced is None or ctx.work_per_chip is None:
        return None
    if ctx.layers is None:  # the configuration names no operations function
        return None
    measured = ctx.reduced.family_self_s(family)
    if not measured:
        return None
    least = flops.mxu_min_seconds(ctx.layers, ctx.work_per_chip, ctx.peaks)
    ctx.notes["mxu_memory_bound_share"] = least["memory_bound_share"]
    return 100.0 * least["seconds"] / measured
