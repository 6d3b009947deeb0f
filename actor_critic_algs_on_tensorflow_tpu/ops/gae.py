"""Generalized Advantage Estimation as a ``lax.scan``.

Capability parity: the reference computes GAE(lambda) advantages over
rollouts for its on-policy trainers (BASELINE.json:5 — "the GAE(lambda)
advantage computation becomes a lax.scan"). The recursion

    delta_t = r_t + gamma * (1 - d_t) * V(s_{t+1}) - V(s_t)
    A_t     = delta_t + gamma * lambda * (1 - d_t) * A_{t+1}

is a linear backward recurrence over the time axis; on TPU we express it
as a reversed ``lax.scan`` so XLA compiles one fused loop instead of a
Python-unrolled graph.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gae_advantages(
    rewards: jax.Array,
    values: jax.Array,
    dones: jax.Array,
    last_value: jax.Array,
    *,
    gamma: float = 0.99,
    lam: float = 0.95,
    terminations: jax.Array | None = None,
    truncation_values: jax.Array | None = None,
):
    """Compute GAE(lambda) advantages and value targets.

    Args:
      rewards: ``[T, ...]`` rewards for steps ``0..T-1``.
      values: ``[T, ...]`` value estimates ``V(s_t)``.
      dones: ``[T, ...]`` episode-boundary flags for step ``t`` (1.0
        where ``s_{t+1}`` began a new episode; cuts the recursion).
      last_value: ``[...]`` value estimate for ``s_T`` (bootstrap).
      gamma: discount factor.
      lam: GAE lambda.
      terminations: optional ``[T, ...]`` flags marking TRUE terminal
        transitions (env reached an absorbing state). Where an episode
        ended by time-limit truncation instead (``dones=1`` but
        ``terminations=0``), the one-step target still bootstraps from
        the truncated state's value — supplied via
        ``truncation_values`` — removing the time-limit bias. When
        omitted, ``dones`` is used (truncation treated as terminal,
        the classic biased-but-simple convention).
      truncation_values: optional ``[T, ...]`` ``V(final_obs_t)`` used
        as the bootstrap at truncated steps (pre-auto-reset obs).

    Returns:
      ``(advantages, returns)`` each ``[T, ...]``; ``returns`` are the
      lambda-returns ``A_t + V(s_t)`` used as value-function targets.
    """
    rewards = jnp.asarray(rewards)
    values = jnp.asarray(values)
    last_value = jnp.asarray(last_value)
    dones = jnp.asarray(dones, dtype=rewards.dtype)
    values_tp1 = jnp.concatenate([values[1:], last_value[None]], axis=0)
    if terminations is None or truncation_values is None:
        # Without V(final_obs) we cannot bootstrap a truncated step
        # correctly, so truncation falls back to terminal treatment.
        bootstrap_cut = dones
    else:
        terminations = jnp.asarray(terminations, dtype=rewards.dtype)
        bootstrap_cut = terminations
        truncated = dones * (1.0 - terminations)
        values_tp1 = jnp.where(
            truncated > 0.5, jnp.asarray(truncation_values), values_tp1
        )
    deltas = rewards + gamma * (1.0 - bootstrap_cut) * values_tp1 - values

    def _step(carry, inp):
        delta, done = inp
        carry = delta + gamma * lam * (1.0 - done) * carry
        return carry, carry

    _, adv_rev = jax.lax.scan(
        _step,
        jnp.zeros_like(last_value),
        (deltas[::-1], dones[::-1]),
    )
    advantages = adv_rev[::-1]
    returns = advantages + values
    return advantages, returns


def discounted_returns(
    rewards: jax.Array,
    dones: jax.Array,
    last_value: jax.Array,
    *,
    gamma: float = 0.99,
):
    """Plain discounted bootstrapped returns (A3C-style n-step targets)."""
    rewards = jnp.asarray(rewards)
    dones = jnp.asarray(dones, dtype=rewards.dtype)

    def _step(carry, inp):
        r, d = inp
        carry = r + gamma * (1.0 - d) * carry
        return carry, carry

    _, ret_rev = jax.lax.scan(_step, last_value, (rewards[::-1], dones[::-1]))
    return ret_rev[::-1]
