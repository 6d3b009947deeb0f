"""Seconds of ``jax.monitoring`` duration events during set-up."""


def read(ctx, events):
    seen = [ctx.setup_compile["seconds"][e] for e in events
            if e in ctx.setup_compile["seconds"]]
    return float(sum(seen)) if seen else None
