"""V-trace off-policy corrected value targets as a ``lax.scan``.

Capability parity: the reference's IMPALA / distributed-A3C mode applies
V-trace correction to actor-generated trajectories (BASELINE.json:11 —
"IMPALA / distributed A3C with V-trace (async actor<->learner over TPU
pod)"). Implements the recursion from Espeholt et al. 2018
("IMPALA: Scalable Distributed Deep-RL ..."), eqs. (1)-(2):

    rho_t  = min(rho_bar, pi(a_t|s_t) / mu(a_t|s_t))
    c_t    = lam * min(c_bar, pi/mu)
    delta_t = rho_t * (r_t + gamma * V(s_{t+1}) - V(s_t))
    vs_t - V(s_t) = delta_t + gamma * c_t * (vs_{t+1} - V(s_{t+1}))

expressed as one reversed ``lax.scan`` so the learner's target
computation compiles to a single fused TPU loop.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class VTraceOutput(NamedTuple):
    vs: jax.Array               # [T, ...] corrected value targets
    pg_advantages: jax.Array    # [T, ...] policy-gradient advantages
    rhos: jax.Array             # [T, ...] unclipped importance ratios


def vtrace(
    behaviour_log_probs: jax.Array,
    target_log_probs: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    bootstrap_value: jax.Array,
    *,
    gamma: float = 0.99,
    lam: float = 1.0,
    rho_bar: float = 1.0,
    c_bar: float = 1.0,
    pg_rho_bar: float | None = None,
) -> VTraceOutput:
    """Compute V-trace targets and policy-gradient advantages.

    All time-major inputs are ``[T, ...]``; ``bootstrap_value`` is
    ``[...]`` = V(s_T) under the target policy.  ``dones`` masks the
    bootstrap across episode boundaries (1.0 where s_{t+1} is a reset).
    """
    rewards = jnp.asarray(rewards)
    values = jnp.asarray(values)
    bootstrap_value = jnp.asarray(bootstrap_value)
    dones = jnp.asarray(dones, dtype=rewards.dtype)
    log_rhos = target_log_probs - behaviour_log_probs
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(rho_bar, rhos)
    cs = lam * jnp.minimum(c_bar, rhos)

    values_tp1 = jnp.concatenate([values[1:], bootstrap_value[None]], axis=0)
    discounts = gamma * (1.0 - dones)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    def _step(acc, inp):
        delta, discount, c = inp
        acc = delta + discount * c * acc
        return acc, acc

    _, acc_rev = jax.lax.scan(
        _step,
        jnp.zeros_like(bootstrap_value),
        (deltas[::-1], discounts[::-1], cs[::-1]),
    )
    vs_minus_v = acc_rev[::-1]
    vs = values + vs_minus_v

    vs_tp1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    clipped_pg_rhos = jnp.minimum(
        rho_bar if pg_rho_bar is None else pg_rho_bar, rhos
    )
    pg_advantages = clipped_pg_rhos * (rewards + discounts * vs_tp1 - values)
    return VTraceOutput(vs=vs, pg_advantages=pg_advantages, rhos=rhos)
