"""Operations the traced window's forward and backward passes require
(nothing recomputed counts), over the window, over chips times peak."""

from perfbench.harness import flops


def read(ctx):
    if ctx.reduced is None or ctx.work_per_chip is None or not ctx.reduced.window_s:
        return None
    if ctx.layers is None:  # the configuration names no operations function
        return None
    need = flops.model_flops(ctx.layers, ctx.work_per_chip)
    return 100.0 * need / ctx.reduced.window_s / ctx.peaks["bf16_flops_per_s"]
