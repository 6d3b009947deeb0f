"""A counter of the program's own log line (``run_impala``'s
``log_fn`` rows inside the window).

``stat``: ``share_pct`` — the counter's seconds summed over the rows,
over the benchmark's clock between the first and last row;
``median`` — the median of the rows' values."""

import statistics


def read(ctx, key, stat):
    rows = [r for r in ctx.log_rows if key in r]
    if not rows or not ctx.log_window_s:
        return None
    if stat == "share_pct":
        return 100.0 * sum(r[key] for r in rows) / ctx.log_window_s
    if stat == "median":
        return float(statistics.median(r[key] for r in rows))
    raise ValueError(f"log_counter: unknown stat {stat!r}")
