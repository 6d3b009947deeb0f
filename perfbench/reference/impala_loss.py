"""Plain float32 IMPALA: V-trace targets and the actor-critic loss.

Source: Espeholt et al. 2018, "IMPALA: Scalable Distributed Deep-RL
with Importance Weighted Actor-Learner Architectures",
arXiv:1802.01561 — sec. 4.1 eq. (1) the V-trace target
v_s = V(x_s) + sum_t gamma^(t-s) (prod c_i) delta_t V with
delta_t V = rho_t (r_t + gamma V(x_{t+1}) - V(x_t)),
rho_t = min(rho_bar, pi/mu), c_i = lambda min(c_bar, pi/mu), in the
recursive form of Remark 1; sec. 4.2 the three gradients: policy
gradient rho_s grad log pi(a_s|x_s) (r_s + gamma v_{s+1} - V(x_s)),
value regression of V(x_s) on v_s, and the entropy bonus.

Departures from the paper, each the program's stated configuration:
the value term is 0.5 * mean squared error times ``vf_coef`` 0.5, means
and not sums over the batch; an episode boundary zeroes the discount
(``dones``); no LSTM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import nature_cnn


def vtrace(behaviour_log_probs, target_log_probs, rewards, values, dones,
           bootstrap_value, gamma, lam, rho_bar, c_bar):
    """``[T, B]`` inputs -> ``(vs, pg_advantages)``; an explicit
    backward loop over time."""
    T = rewards.shape[0]
    rhos = jnp.exp(target_log_probs - behaviour_log_probs)
    clipped_rhos = jnp.minimum(rho_bar, rhos)
    cs = lam * jnp.minimum(c_bar, rhos)
    discounts = gamma * (1.0 - dones)
    acc = jnp.zeros_like(bootstrap_value)
    next_value = bootstrap_value
    corrections = []
    for t in range(T - 1, -1, -1):
        delta = clipped_rhos[t] * (
            rewards[t] + discounts[t] * next_value - values[t]
        )
        acc = delta + discounts[t] * cs[t] * acc
        corrections.append(acc)
        next_value = values[t]
    vs = values + jnp.stack(corrections[::-1])
    vs_next = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    pg_adv = clipped_rhos * (rewards + discounts * vs_next - values)
    return vs, pg_adv


def loss(params, batch, hp):
    """IMPALA's loss on one ``[T, B]`` batch: ``obs [T, B, 84, 84, 4]``
    uint8, ``actions``, ``rewards``, ``dones``, ``behaviour_log_probs``
    ``[T, B]``, ``last_obs [B, 84, 84, 4]``. ``hp``: ``gamma``,
    ``vtrace_lam``, ``rho_bar``, ``c_bar``, ``vf_coef``, ``ent_coef``.
    Returns ``(total, parts)``."""
    T, B = batch["actions"].shape
    flat_obs = batch["obs"].reshape((T * B,) + batch["obs"].shape[2:])
    logits, values = nature_cnn.forward(params, flat_obs)
    log_probs, entropy = nature_cnn.categorical(
        logits, batch["actions"].reshape(-1)
    )
    log_probs, values = log_probs.reshape(T, B), values.reshape(T, B)
    _, last_value = nature_cnn.forward(params, batch["last_obs"])
    stop = jax.lax.stop_gradient
    vs, pg_adv = vtrace(
        batch["behaviour_log_probs"], stop(log_probs), batch["rewards"],
        stop(values), batch["dones"], stop(last_value),
        hp["gamma"], hp["vtrace_lam"], hp["rho_bar"], hp["c_bar"],
    )
    pg = -jnp.mean(log_probs * stop(pg_adv))
    vf = 0.5 * jnp.mean((values - stop(vs)) ** 2)
    ent = jnp.mean(entropy)
    total = pg + hp["vf_coef"] * vf - hp["ent_coef"] * ent
    return total, {"policy_loss": pg, "value_loss": vf, "entropy": ent,
                   "vs": vs, "pg_advantages": pg_adv}
