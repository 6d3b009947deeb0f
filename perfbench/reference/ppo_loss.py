"""Plain float32 PPO: GAE(lambda) and the clipped surrogate objective.

Source: Schulman et al. 2017, "Proximal Policy Optimization
Algorithms", arXiv:1707.06347 — eq. (7) the clipped surrogate
L^CLIP = E[min(r_t A_t, clip(r_t, 1-eps, 1+eps) A_t)], eq. (9) the
combined objective L^CLIP - c1 L^VF + c2 S, eqs. (11)-(12) the
truncated GAE(lambda) estimator of Schulman et al. 2016
(arXiv:1506.02438), and Table 5's Atari values (gamma 0.99, lambda
0.95, horizon 128, c1 1 on 0.5 (v - target)^2, c2 0.01).

Departures from the paper, each the program's stated configuration
and common practice (OpenAI Baselines ppo2): advantages are whitened
over the minibatch; the value loss is clipped around the old value
(``vf_clip``), 0.5 * mean(max((v - R)^2, (v_clip - R)^2)); an episode
boundary cuts both the bootstrap and the recursion (``dones``), time
limits included (``time_limit_bootstrap=False``).
"""

from __future__ import annotations

import jax.numpy as jnp

from perfbench.reference import nature_cnn


def gae(rewards, values, dones, last_value, gamma, lam):
    """``[T, B]`` inputs -> ``(advantages, returns)``; an explicit
    backward loop over time."""
    T = rewards.shape[0]
    adv = []
    carry = jnp.zeros_like(last_value)
    next_value = last_value
    for t in range(T - 1, -1, -1):
        live = 1.0 - dones[t]
        delta = rewards[t] + gamma * live * next_value - values[t]
        carry = delta + gamma * lam * live * carry
        adv.append(carry)
        next_value = values[t]
    advantages = jnp.stack(adv[::-1])
    return advantages, advantages + values


def loss(params, batch, hp):
    """The PPO objective as a loss on one flat batch.

    ``batch``: ``obs [N, 84, 84, 4]`` uint8, ``actions``,
    ``old_log_probs``, ``old_values``, ``advantages``, ``returns``
    ``[N]``. ``hp``: ``clip_eps``, ``vf_coef``, ``ent_coef``.
    Returns ``(total, parts)``."""
    logits, values = nature_cnn.forward(params, batch["obs"])
    log_probs, entropy = nature_cnn.categorical(logits, batch["actions"])
    adv = batch["advantages"]
    adv = (adv - jnp.mean(adv)) / jnp.sqrt(
        jnp.mean((adv - jnp.mean(adv)) ** 2) + 1e-8
    )
    ratio = jnp.exp(log_probs - batch["old_log_probs"])
    eps = hp["clip_eps"]
    surrogate = jnp.minimum(
        ratio * adv, jnp.clip(ratio, 1.0 - eps, 1.0 + eps) * adv
    )
    policy_loss = -jnp.mean(surrogate)
    clipped = batch["old_values"] + jnp.clip(
        values - batch["old_values"], -eps, eps
    )
    vf = 0.5 * jnp.mean(jnp.maximum(
        (values - batch["returns"]) ** 2, (clipped - batch["returns"]) ** 2
    ))
    ent = jnp.mean(entropy)
    total = policy_loss + hp["vf_coef"] * vf - hp["ent_coef"] * ent
    return total, {"policy_loss": policy_loss, "value_loss": vf,
                   "entropy": ent}
