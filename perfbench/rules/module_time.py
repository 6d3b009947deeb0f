"""Device time of one compiled program, from the trace's program line.

``match``: regex on the program's name; ``stat``: ``median_ms`` (one
execution) or ``share_pct`` (all executions over the device's busy
time)."""

import re
import statistics


def read(ctx, match, stat):
    if ctx.reduced is None:
        return None
    durs = [
        d for name, ds in ctx.reduced.modules.items()
        if re.search(match, name) for d in ds
    ]
    if not durs:
        return None
    if stat == "median_ms":
        return statistics.median(durs) * 1e3
    if stat == "share_pct":
        return 100.0 * sum(durs) / ctx.reduced.chips / ctx.reduced.busy_s
    raise ValueError(f"module_time: unknown stat {stat!r}")
