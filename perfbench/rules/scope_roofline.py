"""Roofline share of one scope of the program: the least time the chip
could take for the named ``Layer`` rows' work in the traced window
(``harness/flops.py::mxu_min_seconds``: for each product the larger of
operations over peak FLOP/s and bytes over peak bytes/s), over the self
time the trace shows for the operations traced under
``jax.named_scope(scope)`` — or, where ``family`` is given instead, for
that family of operations by name (``op_family_share``'s kind of
argument). The family is for kernels the compiler names itself: the
TPU's grouped products are all ``ragged-dot-none`` and reach a scope
only through whatever consumes them, so under the scope alone a third
of their time read as dispatch and optimizer and the share as 106 %
(my chip run, PR 27).

``layers``: names of rows of the configuration's operations function,
counted for every forward and training pass of the window.
``rollout_only``: rows counted for the acting forward passes alone
(a recurrent state's bytes, which only the step form moves a token).

The join of operations to scopes is ``scope_lowering``'s, with its own
checks; where it stands but nothing ran under the scope, where the
configuration names no operations function, or where a named row is
missing, there is nothing to read.
"""

from perfbench.harness import flops
from perfbench.rules import scope_lowering


def read(ctx, layers, scope=None, family=None, rollout_only=()):
    if ctx.reduced is None or ctx.work_per_chip is None:
        return None
    if ctx.layers is None:
        return None
    rows = {layer.name: layer for layer in ctx.layers}
    if not set(layers) | set(rollout_only) <= set(rows):
        return None
    if family is not None:
        measured = ctx.reduced.family_self_s(family)
    else:
        joined = scope_lowering.scope_join(ctx)
        if joined.get("why"):
            return None
        measured = sum(
            self_s for name, self_s in joined["self_s_by_phases"].items()
            if scope in name.split("/")
        ) / max(ctx.reduced.chips, 1)
    if not measured:
        return None
    work = ctx.work_per_chip
    acting = {k: work.get(k, 0) for k in ("forward_samples", "forward_calls")}
    least = flops.mxu_min_seconds(
        [rows[n] for n in layers], work, ctx.peaks
    )["seconds"] + flops.mxu_min_seconds(
        [rows[n] for n in rollout_only], acting, ctx.peaks
    )["seconds"]
    ctx.notes.setdefault("scope_roofline", {})[scope or layers[0]] = {
        "least_s": least, "measured_s": measured,
    }
    return 100.0 * least / measured
