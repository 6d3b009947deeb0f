"""Share of the device's busy time spent in one phase of the program
(self times, as ``op_family_share``): the traced operations whose
instruction the program traced under ``jax.named_scope(scope)``.

``scope``: a phase of ``utils/profiling.py`` (an operation under
``rollout/env_step`` counts for both), or ``null`` for the operations
under no declared phase. ``without``: phases whose operations are left
out, where one phase is traced inside another and two metrics are to
add up (IMPALA's ``minibatch_prep`` and ``advantage`` run inside the
differentiated function, so inside ``loss_grad``).

``scope_lowering`` makes the join and says in
``notes["scope_join"]`` why there is nothing to read when there is
not. Where the join stands, a phase whose operations took no time of
their own (the compiler fused them into another phase's) reads 0.
"""

from perfbench.rules import scope_lowering


def read(ctx, scope, without=()):
    joined = scope_lowering.scope_join(ctx)
    if joined.get("why") or not ctx.reduced.busy_s:
        return None
    seconds = 0.0
    for name, self_s in joined["self_s_by_phases"].items():
        phases = () if name == scope_lowering.NO_PHASE else name.split("/")
        wanted = scope in phases if scope is not None else not phases
        if wanted and not set(without) & set(phases):
            seconds += self_s
    return 100.0 * seconds / max(ctx.reduced.chips, 1) / ctx.reduced.busy_s
