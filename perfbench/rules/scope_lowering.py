"""Which phase of the program each traced device operation belongs to.

The program names its phases with ``jax.named_scope``
(``utils/profiling.py``: rollout, env_step, advantage, update,
minibatch_prep, loss_grad, optimizer, ...), and a scope reaches the
compiled program as each instruction's ``op_name``. The trace the
harness keeps (``ctx.reduced.op_events``) has the operations' names
and self times but no ``op_name`` and no program, so the phases come
by a join: the cell's programs are lowered and compiled again from
abstract arguments with the program's own factories (one compile a
checkout, then a read of the compile cache; not the run's own entry,
``compile_cache.metadata_in_key`` says why), ``profiling.scope_table``
reads the phases out of the compiled text (an instruction the compiler
made has no ``op_name`` and inherits its consumer's), and an operation
finds its instruction by ``join_key``. Once a run, after the window,
in traced runs only; the result is kept on ``ctx.notes["scope_join"]``.

The join checks itself, because a wrong join must read as no number
and never as a number: it gives nothing when a lowered program is not
among the trace's programs, or when less than ``MIN_COVERAGE_PCT`` of
the traced self time found its instruction. A key that two programs
hold under different phases counts as not found.

A program without the phases (a checkout from before they were
added) gives nothing either, and nothing is lowered for it.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

MIN_COVERAGE_PCT = 99.0
NO_PHASE = "(none)"
_KEY = re.compile(r"^(%[\w.\-]+ = .*?) [a-z][\w\-]*\(")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)


def join_key(instruction: str) -> str:
    """An instruction's name and result shape: ``%copy.47 =
    bf16[8192,84,84,4]{0,3,2,1:T(4,128)(2,1)}``. The trace prints the
    rest of the text its own way (operand shapes, no ``/*index=*/``
    marks among operands, ``async-start`` for ``slice-start``), so
    the rest is left out of the join."""
    m = _KEY.match(instruction)
    return m.group(1) if m else instruction


Table = Dict[str, Optional[Tuple[str, ...]]]


def merge_tables(tables: List[Table]) -> Table:
    """One table by ``join_key`` over all of a cell's programs; a key
    under two different phase lists is ``None``."""
    merged: Table = {}
    for table in tables:
        for text, phases in table.items():
            key = join_key(text)
            if merged.setdefault(key, phases) != phases:
                merged[key] = None
    return merged


def join(op_events, tables, program_names, traced_programs) -> dict:
    """Traced self time by phase list. ``op_events``: ``(event, self
    ns)`` over all chips; ``tables``: one ``scope_table`` a lowered
    program; ``program_names``: their ``HloModule`` names;
    ``traced_programs``: the trace's program names
    (``jit_local_iteration(<fingerprint>)``)."""
    out = {"programs": list(program_names), "why": None}
    traced = {name.split("(")[0] for name in traced_programs}
    absent = [p for p in program_names if p not in traced]
    if absent:
        out["why"] = (f"lowered program(s) {absent} are not among the "
                      f"trace's {sorted(traced)}")
        return out
    merged = merge_tables(tables)
    out["ambiguous_keys"] = sum(v is None for v in merged.values())
    by_phases: Dict[str, float] = {}
    total = found = 0.0
    for event, self_ns in op_events:
        total += self_ns
        phases = merged.get(join_key(event.name))
        if phases is None:
            continue
        found += self_ns
        name = "/".join(phases) or NO_PHASE
        by_phases[name] = by_phases.get(name, 0.0) + self_ns
    out["coverage_pct"] = 100.0 * found / total if total else 0.0
    out["self_s_by_phases"] = {k: v / 1e9 for k, v in by_phases.items()}
    if out["coverage_pct"] < MIN_COVERAGE_PCT:
        out["why"] = (f"only {out['coverage_pct']:.2f} % of the traced "
                      f"self time found its instruction in the lowered "
                      f"programs (< {MIN_COVERAGE_PCT} %)")
    return out


def scope_join(ctx) -> dict:
    """``ctx.notes["scope_join"]``, made on first use."""
    if "scope_join" not in ctx.notes:
        ctx.notes["scope_join"] = _scope_join(ctx)
    return ctx.notes["scope_join"]


def _scope_join(ctx) -> dict:
    if ctx.reduced is None:
        return {"why": "no device operation in the trace"}
    try:
        from actor_critic_algs_on_tensorflow_tpu.utils.compile_cache import (
            metadata_in_key,
        )
        from actor_critic_algs_on_tensorflow_tpu.utils.profiling import (
            scope_table,
        )
    except ImportError:
        return {"why": "this program declares no phases "
                       "(no utils/profiling.py::scope_table)"}
    lower = _LOWER.get(ctx.cell.family)
    if lower is None:
        return {"why": f"no lowering for family {ctx.cell.family!r}"}
    # (the persistent cache keys a program without its metadata: an
    # entry from before the phases were named, the parent commit's run
    # on the same machine, would come back without them.)
    with metadata_in_key():
        texts = lower(ctx.runner)
    names = [_MODULE.search(t).group(1) for t in texts]
    tables = [scope_table(t) for t in texts]
    if not any(phases for t in tables for phases in t.values()):
        return {"programs": names,
                "why": "the compiled programs carry no declared phase"}
    return join(ctx.reduced.op_events, tables, names, ctx.reduced.modules)


def _lower_ppo(runner) -> List[str]:
    """The fused iteration, built again from the runner's config (the
    runner's own jitted function would hand back the executable it
    already runs, whatever cache that came from), from the state's
    shapes and the shardings ``common.state_specs`` gives them."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from actor_critic_algs_on_tensorflow_tpu.algos import common
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    fns = make_ppo(runner.cfg)
    state = jax.eval_shape(fns.init, jax.random.PRNGKey(0))
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(fns.mesh, spec),
        common.state_specs(state),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )
    args = jax.tree_util.tree_map(
        lambda x, sharding: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding
        ),
        state, shardings,
    )
    return [fns.iteration.lower(args).compile().as_text()]


def _lower_impala(runner) -> List[str]:
    """The donated learner step and actor 0's rollout, built again
    from the runner's config."""
    import jax

    from actor_critic_algs_on_tensorflow_tpu.algos.impala import (
        make_impala,
        stack_trajectories,
    )

    progs = make_impala(runner.cfg)
    rollout, env_reset = progs.make_actor_programs(0)
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(progs.init, key)
    env_state, obs, carry = jax.eval_shape(env_reset, key)
    actor_args = (state.params, env_state, obs, carry, key)
    traj = jax.eval_shape(rollout, *actor_args)[3]
    batch = jax.eval_shape(
        lambda t: stack_trajectories([t] * runner.cfg.batch_trajectories),
        traj,
    )
    return [
        progs.learner_step_donated.lower(state, batch).compile().as_text(),
        rollout.lower(*actor_args).compile().as_text(),
    ]


_LOWER = {"ppo": _lower_ppo, "impala": _lower_impala}
