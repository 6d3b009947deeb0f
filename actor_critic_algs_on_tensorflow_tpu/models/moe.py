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
expert) pairs that land here are sorted by expert into one buffer of
``moe_capacity(tokens)`` rows, shared by the held experts, the products
run grouped over it (``lax.ragged_dot``), and the pairs that did not fit
are counted (``stats["moe_overflow_pairs"]``), as are the held experts a
call gave no row at all (``stats["moe_experts_touched_share"]``: their
weights are not read) and the share of the buffer's rows that hold a
pair (``stats["moe_buffer_fill_share"]``: gather, grouped products and
scatter-add run over every row). Each model keeps its routing function
and its shared branch (Qwen3-Next: softmax scores, a sigmoid-gated
shared expert; Kimi-VL: sigmoid scores with a selection bias, an
ungated one; SDAR: Qwen3-Next's routing, ``route_softmax_top_k``, no
shared expert).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from actor_critic_algs_on_tensorflow_tpu.utils import profiling

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


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

    def moe_capacity(self, tokens: int) -> int:
        expected = tokens * self.top_k * self.experts_held / self.num_experts
        rows = min(math.ceil(self.capacity_factor * expected),
                   tokens * self.top_k)
        return max(8, -(-rows // 8) * 8)


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


def routed_experts(p, x, spec: ExpertSpec, dtype, route):
    """``x [N, H]`` -> ``(y [N, H], stats)``: the held experts' terms
    of the routed sum, dropless within the dispatch buffer. ``p`` holds
    the held experts' ``w_gate``, ``w_up`` ``[held, H, I]`` and
    ``w_down [held, I, H]``, and whatever ``route`` reads."""
    N, k, held = x.shape[0], spec.top_k, spec.experts_held
    with jax.named_scope(profiling.MOE_ROUTER):
        experts, weights = route(p, x)
    with jax.named_scope(profiling.MOE_DISPATCH):
        # Pairs sorted by local expert, the other chips' last; the
        # first `rows` of that order are the buffer.
        local = experts.reshape(-1) - spec.first_expert
        mine = (local >= 0) & (local < held)
        local = jnp.where(mine, local, held)
        rows = spec.moe_capacity(N)
        order = jnp.argsort(local, stable=True)[:rows]
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

        h = jax.nn.silu(grouped(xs, p["w_gate"])) * grouped(xs, p["w_up"])
        ys = grouped(h, p["w_down"])
        ys = jnp.where(row_valid[:, None], ys, 0.0) * row_weight[:, None]
    with jax.named_scope(profiling.MOE_DISPATCH), jax.named_scope(
        profiling.MOE_COMBINE
    ):
        routed = jnp.zeros((N, x.shape[1]), _F32).at[row_token].add(ys)
    load = group_sizes.astype(_F32)
    stats = {
        "moe_local_pairs_per_token": n_mine.astype(_F32) / N,
        "moe_expert_load_max_over_mean":
            jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
        "moe_overflow_pairs": (n_mine - kept).astype(_F32),
        # the held experts this call gave a row: the ones whose weights
        # the grouped products read
        "moe_experts_touched_share": jnp.mean((group_sizes > 0).astype(_F32)),
        # the dispatch buffer's rows that hold a pair: the grouped
        # products and the gather and scatter around them run over all
        # of its rows
        "moe_buffer_fill_share": kept.astype(_F32) / rows,
    }
    return routed, stats


def reduce_moe_stats(stats):
    """One row of counters from many (layers, steps, minibatches, any
    leading axes): mean pairs a token, max imbalance, summed overflow,
    mean share of the held experts a call touched, mean share of the
    dispatch buffer's rows that held a pair."""
    return {
        "moe_local_pairs_per_token":
            jnp.mean(stats["moe_local_pairs_per_token"]),
        "moe_expert_load_max_over_mean":
            jnp.max(stats["moe_expert_load_max_over_mean"]),
        "moe_overflow_pairs": jnp.sum(stats["moe_overflow_pairs"]),
        "moe_experts_touched_share":
            jnp.mean(stats["moe_experts_touched_share"]),
        "moe_buffer_fill_share": jnp.mean(stats["moe_buffer_fill_share"]),
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
    products is one of them), the dispatch buffer's fill as each of the
    two saw it (their buffers differ in rows by orders of magnitude)."""
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
    }
