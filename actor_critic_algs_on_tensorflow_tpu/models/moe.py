"""The expert layer the sequence cores share (``models/qwen3_next.py``,
``models/kimi_vl.py``, ``models/sdar.py``): a chip's share of a routed
mixture of SwiGLU experts, dropless within one dispatch buffer, with its
counters; and the small pieces the cores build their layers from
(``mm``, ``Params``, the softmax-top-k routing two of them bind).

The layer is told which experts it holds (``ExpertSpec``:
``first_expert``, ``experts_held`` of ``num_experts``). It routes over
all of them with the model's own routing function — ``route(p, x) ->
(experts [N, k], weights [N, k])``, the top-k of ALL experts and the
weight each term is summed under — and computes only the terms of its
own experts; what the absent ones would add is left out. The (token,
expert) pairs that land here are sorted by expert into one buffer of at
most ``moe_capacity(tokens)`` rows, shared by the held experts, the
products run grouped over it (``lax.ragged_dot``), and the pairs that
did not fit are counted (``stats["moe_overflow_pairs"]``), as are the
held experts a call gave no row at all
(``stats["moe_experts_touched_share"]``: their weights are not read).
Gather, grouped products and scatter-add run over every row of the
buffer, filled or not, so where the capacity is several times the
expected pairs (SDAR's call sites) a call runs at the lowest rung of a
short static ladder of row counts that holds the pairs it counted
(``ExpertSpec.buffer_ladder``, ``lax.switch``); the share of those rows
that hold a pair is ``stats["moe_buffer_fill_share"]`` and the rung's
rows over the capacity ``stats["moe_buffer_rows_used_share"]``. Each
model keeps its routing function
and its shared branch (Qwen3-Next: softmax scores, a sigmoid-gated
shared expert; Kimi-VL: sigmoid scores with a selection bias, an
ungated one; SDAR: Qwen3-Next's routing, ``route_softmax_top_k``, no
shared expert).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
# The dispatch buffer's lower rungs, as multiples of the expected local
# pairs (PERF.md section 6, PR 36: what the chip paid for the rows of a
# buffer sized for the worst routing, and how often each rung ran).
_LADDER_FACTORS = (1.5, 3.0)


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """What ``routed_experts`` has to know of a model's config."""

    num_experts: int
    top_k: int
    first_expert: int
    experts_held: int
    # Rows of the dispatch buffer over the expected number of local
    # pairs, tokens * top_k * held / num_experts.
    capacity_factor: float

    def _rows(self, tokens: int, factor: float) -> int:
        expected = tokens * self.top_k * self.experts_held / self.num_experts
        rows = min(math.ceil(factor * expected), tokens * self.top_k)
        return max(8, -(-rows // 8) * 8)

    def moe_capacity(self, tokens: int) -> int:
        return self._rows(tokens, self.capacity_factor)

    def buffer_ladder(self, tokens: int) -> tuple:
        """The row counts a call may run its buffer at, ascending, the
        last ``moe_capacity(tokens)``: below it ``_LADDER_FACTORS``
        times the expected local pairs, each kept only where it is at
        most half the capacity (none at ``capacity_factor`` 2.0)."""
        top = self.moe_capacity(tokens)
        lower = sorted({self._rows(tokens, f) for f in _LADDER_FACTORS})
        return (*(rows for rows in lower if 2 * rows <= top), top)


class Params(nn.Module):
    """A named group of float32 parameters (a decoder layer's)."""

    spec: Any  # {name: (shape, init)}, hashable as a tuple of items

    @nn.compact
    def __call__(self):
        return {name: self.param(name, init, shape, _F32)
                for name, (shape, init) in self.spec}


def mm(x, w, dtype):
    """A matrix product in ``dtype`` with float32 accumulation."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=_F32)


def swiglu(x, w_gate, w_up, w_down, dtype):
    return mm(jax.nn.silu(mm(x, w_gate, dtype)) * mm(x, w_up, dtype),
              w_down, dtype)


def route_softmax_top_k(p, x, top_k: int, renormalise: bool):
    """``x [N, H]`` -> the ``top_k`` experts ``[N, k]`` of ALL the
    router's outputs by softmax probability and their weights (summing
    to 1 where ``renormalise``), float32 throughout."""
    logits = jnp.dot(x.astype(_F32), p["router"], precision=_HIGHEST)
    probs = jax.nn.softmax(logits, -1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return experts, weights


def _buffer_at(rows, spec, dtype, x, w_gate, w_up, w_down, weights,
               by_expert, local, mine):
    """The layer from the sorted pairs on, through a dispatch buffer of
    ``rows`` rows (static): the first ``rows`` of ``by_expert`` (the
    ``N * k`` pairs' indices sorted by ``local``, the local expert,
    ``held`` for another chip's) gathered, the grouped products, the
    mask and the weighted scatter-add back -> ``(routed [N, H], the
    held experts' rows, the local pairs, those of them in the
    buffer)``."""
    N, k, held = x.shape[0], spec.top_k, spec.experts_held
    with jax.named_scope(profiling.MOE_DISPATCH):
        order = by_expert[:rows]
        row_expert = local[order]
        row_valid = row_expert < held
        row_token = order // k
        group_sizes = jnp.bincount(row_expert, length=held + 1)[:held]
        group_sizes = group_sizes.astype(jnp.int32)
        row_weight = jnp.where(row_valid, weights.reshape(-1)[order], 0.0)
        # What ragged_dot leaves in rows past the last group is not
        # specified: they go in as zeros and come out masked, so that
        # neither they nor their gradient reach a token.
        xs = jnp.where(
            row_valid[:, None], jnp.take(x, row_token, axis=0), 0.0
        ).astype(dtype)
        n_mine = jnp.sum(mine)
        kept = jnp.sum(row_valid)
    with jax.named_scope(profiling.MOE_EXPERTS):
        def grouped(a, w):
            return jax.lax.ragged_dot(
                a.astype(dtype), w.astype(dtype), group_sizes,
                preferred_element_type=_F32,
            )

        h = jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up)
        ys = grouped(h, w_down)
        ys = jnp.where(row_valid[:, None], ys, 0.0) * row_weight[:, None]
    with jax.named_scope(profiling.MOE_DISPATCH), jax.named_scope(
        profiling.MOE_COMBINE
    ):
        routed = jnp.zeros((N, x.shape[1]), _F32).at[row_token].add(ys)
    return routed, group_sizes, n_mine, kept


def _rungs(spec, dtype, tokens):
    return [functools.partial(_buffer_at, rows, spec, dtype)
            for rows in spec.buffer_ladder(tokens)]


def _cast_experts(floats, dtype):
    """``(x, w_gate, w_up, w_down, weights)`` with the experts' weights
    in ``dtype``."""
    x, *experts_w, weights = floats
    with jax.named_scope(profiling.MOE_EXPERTS):
        return (x, *(w.astype(dtype) for w in experts_w), weights)


# The two switches are traced once a (spec, dtype, shapes) and inlined
# where they are called (`inline=True`): six layers, and a rollout's
# pass and the bootstrap pass after it, share one trace of the ladder's
# branches (3 s of set-up on the chip's host otherwise), and every call
# site still lowers its own copy under its own scopes — as a called
# function shared by sites under several scopes, the compiled text
# mixes or drops the callers' names, and the trace reader's join with
# them (PERF.md section 6, PR 36).
_inlined = functools.partial(jax.jit, static_argnums=(0, 1), inline=True)


@_inlined
def _switch(spec, dtype, rung, floats, pairs):
    return jax.lax.switch(
        rung, _rungs(spec, dtype, floats[0].shape[0]),
        *_cast_experts(floats, dtype), *pairs,
    )


@_inlined
def _switch_transposed(spec, dtype, rung, floats, pairs, g):
    def transposed(at_rows, floats, pairs, g):
        _, vjp = jax.vjp(
            lambda *f: at_rows(*f, *pairs)[0], *_cast_experts(floats, dtype)
        )
        return vjp(g)

    # What the update's peak allows, found on the described-v5e compile
    # of the iteration (PERF.md section 6, PR 36): the branches read the
    # parameters themselves and cast inside (a cast made outside is held
    # from the forward on), leave the weights' gradients in `dtype`, as
    # the single buffer's transpose does (asked for in float32, they
    # are 1.3 GB more at the peak), and the gradients pass a barrier
    # (without it the compiler moves the cast, the clip's norm and the
    # update into every branch: 1.4 GB). Behind the barrier that
    # rounding to `dtype` is real; in the single-buffer program the
    # TPU's compiler elides the cast and its inverse.
    grads = jax.lax.optimization_barrier(jax.lax.switch(
        rung,
        [functools.partial(transposed, at_rows)
         for at_rows in _rungs(spec, dtype, floats[0].shape[0])],
        floats, pairs, g,
    ))
    return tuple(d.astype(f.dtype) for d, f in zip(grads, floats))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _buffer_at_rung(spec, dtype, rung, floats, pairs):
    """``_buffer_at`` at the ladder's rung ``rung`` (traced), as
    ``lax.switch`` over the row counts. ``floats``: ``(x, w_gate, w_up,
    w_down, weights)``; ``pairs``: ``(by_expert, local, mine)``. The
    experts' weights are cast before the ``switch``: a rollout's loop
    can hoist the cast of its constant weights, but not out of a branch.

    Its derivative is its own: the forward keeps the arguments and the
    backward switches on the same rung over each rung's ``jax.vjp``.
    Differentiated as it stands, the forward switch would return every
    rung's residuals, those of the rungs not taken as zeros, and a call
    would write the full-size buffers it was there to avoid. Inside a
    layer's ``jax.checkpoint`` this costs nothing: the recomputed
    forward feeds nothing but the counters and goes."""
    return _switch(spec, dtype, rung, floats, pairs)


def _buffer_at_rung_fwd(spec, dtype, rung, floats, pairs):
    return _switch(spec, dtype, rung, floats, pairs), (rung, floats, pairs)


def _buffer_at_rung_bwd(spec, dtype, saved, cotangents):
    rung, floats, pairs = saved
    grads = _switch_transposed(spec, dtype, rung, floats, pairs, cotangents[0])
    return None, grads, None


_buffer_at_rung.defvjp(_buffer_at_rung_fwd, _buffer_at_rung_bwd)


def routed_experts(p, x, spec: ExpertSpec, dtype, route):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum, dropless within the dispatch buffer. ``p`` holds
    the held experts' ``w_gate``, ``w_up`` ``[held, H, I]`` and
    ``w_down [held, I, H]``, and whatever ``route`` reads.

    The buffer's rows are chosen a call: the lowest rung of
    ``spec.buffer_ladder(N)`` that holds the local pairs the call
    counted, so a lower rung never drops a pair and the top one is
    ``spec.moe_capacity(N)``, where the overflow counter counts. With
    one rung (``capacity_factor`` 2.0) there is no ``switch``."""
    N, held = x.shape[0], spec.experts_held
    with jax.named_scope(profiling.MOE_ROUTER):
        experts, weights = route(p, x)
    with jax.named_scope(profiling.MOE_DISPATCH):
        # Pairs sorted by local expert, the other chips' last; the
        # first `rows` of that order are the buffer.
        local = experts.reshape(-1) - spec.first_expert
        mine = (local >= 0) & (local < held)
        local = jnp.where(mine, local, held)
        by_expert = jnp.argsort(local, stable=True)
    ladder = spec.buffer_ladder(N)
    experts_w = (p["w_gate"], p["w_up"], p["w_down"])
    pairs = (by_expert, local, mine)
    if len(ladder) == 1:
        rows = ladder[0]
        routed, group_sizes, n_mine, kept = _buffer_at(
            rows, spec, dtype, x, *experts_w, weights, *pairs
        )
    else:
        with jax.named_scope(profiling.MOE_DISPATCH):
            rung = jnp.sum(jnp.sum(mine) > jnp.asarray(ladder[:-1]))
            rows = jnp.asarray(ladder, _F32)[rung]
        routed, group_sizes, n_mine, kept = _buffer_at_rung(
            spec, dtype, rung, (x, *experts_w, weights), pairs
        )
    load = group_sizes.astype(_F32)
    stats = {
        "moe_local_pairs_per_token": n_mine.astype(_F32) / N,
        "moe_expert_load_max_over_mean":
            jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
        "moe_overflow_pairs": (n_mine - kept).astype(_F32),
        # the held experts this call gave a row: the ones whose weights
        # the grouped products read
        "moe_experts_touched_share": jnp.mean((group_sizes > 0).astype(_F32)),
        # the rows of the buffer this call ran at that hold a pair: the
        # grouped products and the gather and scatter around them run
        # over all of its rows
        "moe_buffer_fill_share": kept.astype(_F32) / rows,
        # and those rows over the most it may hold, moe_capacity(N): 1
        # where there is no ladder or the top rung ran
        "moe_buffer_rows_used_share": jnp.asarray(rows / ladder[-1], _F32),
    }
    return routed, stats


def reduce_moe_stats(stats):
    """One row of counters from many (layers, steps, minibatches, any
    leading axes): mean pairs a token, max imbalance, summed overflow,
    mean share of the held experts a call touched, mean share of the
    dispatch buffer's rows that held a pair, mean share of the capacity
    the buffer ran at."""
    return {
        "moe_local_pairs_per_token":
            jnp.mean(stats["moe_local_pairs_per_token"]),
        "moe_expert_load_max_over_mean":
            jnp.max(stats["moe_expert_load_max_over_mean"]),
        "moe_overflow_pairs": jnp.sum(stats["moe_overflow_pairs"]),
        "moe_experts_touched_share":
            jnp.mean(stats["moe_experts_touched_share"]),
        "moe_buffer_fill_share": jnp.mean(stats["moe_buffer_fill_share"]),
        "moe_buffer_rows_used_share":
            jnp.mean(stats["moe_buffer_rows_used_share"]),
    }


def stack_layer_stats(all_stats):
    """The expert layers' counters of one forward call as one row."""
    return reduce_moe_stats(
        jax.tree_util.tree_map(lambda *x: jnp.stack(x), *all_stats)
    )


def iteration_moe_stats(rollout_stats, update_stats, axis_name):
    """The expert layer's counters of one training iteration,
    replicated over ``axis_name``: pairs a token and load imbalance as
    the update saw them, overflow summed over the rollout's steps and
    the update's blocks (it must be 0), the held experts touched as
    the rollout's steps saw it (nearly every call of the grouped
    products is one of them), the dispatch buffer's fill and the share
    of its capacity it ran at as each of the two saw them (their buffers
    differ in rows by orders of magnitude)."""
    roll, upd = map(reduce_moe_stats, (rollout_stats, update_stats))
    return {
        "moe_local_pairs_per_token": jax.lax.pmean(
            upd["moe_local_pairs_per_token"], axis_name
        ),
        "moe_expert_load_max_over_mean": jax.lax.pmax(
            upd["moe_expert_load_max_over_mean"], axis_name
        ),
        "moe_overflow_pairs": jax.lax.psum(
            roll["moe_overflow_pairs"] + upd["moe_overflow_pairs"], axis_name
        ),
        "moe_experts_touched_share": jax.lax.pmean(
            roll["moe_experts_touched_share"], axis_name
        ),
        "moe_buffer_fill_share_rollout": jax.lax.pmean(
            roll["moe_buffer_fill_share"], axis_name
        ),
        "moe_buffer_fill_share_update": jax.lax.pmean(
            upd["moe_buffer_fill_share"], axis_name
        ),
        "moe_buffer_rows_used_share_rollout": jax.lax.pmean(
            roll["moe_buffer_rows_used_share"], axis_name
        ),
        "moe_buffer_rows_used_share_update": jax.lax.pmean(
            upd["moe_buffer_rows_used_share"], axis_name
        ),
    }
