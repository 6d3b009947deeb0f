"""Per-layer readers. Each module has one function
``read(ctx, **args) -> float | None``; ``args`` come from the metric's
declaration in ``perfbench/metrics/<name>.json``. A reader that finds
nothing to read returns None and the metric is left out of the line."""
