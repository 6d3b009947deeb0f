"""The share of the rows' clock that the median row interval does not
account for (``harness/rows.py::pause_share``): what a rare pause of
the run loop costs, which the median-based throughput leaves out."""

from perfbench.harness import rows


def read(ctx):
    if len(ctx.row_times_s) < 3:
        return None
    return rows.pause_share(ctx.row_times_s)
