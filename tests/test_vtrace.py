"""V-trace scan vs. a direct numpy transcription of the IMPALA paper
recursion (SURVEY.md §4.1)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.ops import vtrace


def _vtrace_oracle(
    behaviour_logp, target_logp, rewards, values, dones, bootstrap,
    gamma, lam, rho_bar, c_bar,
):
    T = len(rewards)
    rhos = np.exp(target_logp - behaviour_logp)
    clipped_rhos = np.minimum(rho_bar, rhos)
    cs = lam * np.minimum(c_bar, rhos)
    discounts = gamma * (1.0 - dones)
    values_tp1 = np.concatenate([values[1:], [bootstrap]])
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    vs_minus_v = np.zeros(T + 1)
    for t in reversed(range(T)):
        vs_minus_v[t] = deltas[t] + discounts[t] * cs[t] * vs_minus_v[t + 1]
    vs = values + vs_minus_v[:T]
    vs_tp1 = np.concatenate([vs[1:], [bootstrap]])
    pg_adv = clipped_rhos * (rewards + discounts * vs_tp1 - values)
    return vs, pg_adv


@pytest.mark.parametrize("seed", [0, 7])
@pytest.mark.parametrize("rho_bar,c_bar", [(1.0, 1.0), (2.0, 0.9)])
def test_vtrace_matches_oracle(seed, rho_bar, c_bar):
    rng = np.random.default_rng(seed)
    T = 13
    b_logp = rng.normal(size=T).astype(np.float32) * 0.3
    t_logp = rng.normal(size=T).astype(np.float32) * 0.3
    rewards = rng.normal(size=T).astype(np.float32)
    values = rng.normal(size=T).astype(np.float32)
    dones = (rng.random(T) < 0.2).astype(np.float32)
    bootstrap = np.float32(rng.normal())

    out = vtrace(
        jnp.asarray(b_logp),
        jnp.asarray(t_logp),
        jnp.asarray(rewards),
        jnp.asarray(values),
        jnp.asarray(dones),
        jnp.asarray(bootstrap),
        gamma=0.99,
        lam=0.97,
        rho_bar=rho_bar,
        c_bar=c_bar,
    )
    vs_np, pg_np = _vtrace_oracle(
        b_logp, t_logp, rewards, values, dones, bootstrap, 0.99, 0.97,
        rho_bar, c_bar,
    )
    np.testing.assert_allclose(np.asarray(out.vs), vs_np, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(out.pg_advantages), pg_np, rtol=1e-4, atol=1e-5
    )


@pytest.mark.parametrize("T,B", [(1, 1), (7, 3), (128, 5)])
def test_vtrace_batched_matches_oracle_columnwise(T, B):
    """``[T, B]`` under ``jax.jit``, as the learner calls it: every
    column is the oracle's answer for that env alone."""
    rng = np.random.default_rng(T * 31 + B)
    b_logp = rng.normal(size=(T, B)).astype(np.float32) * 0.3
    t_logp = rng.normal(size=(T, B)).astype(np.float32) * 0.3
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    values = rng.normal(size=(T, B)).astype(np.float32)
    dones = (rng.random((T, B)) < 0.2).astype(np.float32)
    bootstrap = rng.normal(size=B).astype(np.float32)

    out = jax.jit(
        functools.partial(vtrace, gamma=0.99, lam=0.97, rho_bar=2.0, c_bar=0.9)
    )(b_logp, t_logp, rewards, values, dones, bootstrap)
    assert out.vs.shape == (T, B) and out.vs.dtype == jnp.float32
    for b in range(B):
        vs_np, pg_np = _vtrace_oracle(
            b_logp[:, b], t_logp[:, b], rewards[:, b], values[:, b],
            dones[:, b], bootstrap[b], 0.99, 0.97, 2.0, 0.9,
        )
        np.testing.assert_allclose(out.vs[:, b], vs_np, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            out.pg_advantages[:, b], pg_np, rtol=1e-4, atol=1e-5
        )


def test_vtrace_on_policy_reduces_to_gae_lambda1():
    """With pi == mu and lam=1, rho=c=1 and vs_t equals the lambda=1
    GAE return (bootstrapped Monte-Carlo lambda-return)."""
    from actor_critic_algs_on_tensorflow_tpu.ops import gae_advantages

    rng = np.random.default_rng(5)
    T = 9
    logp = rng.normal(size=T).astype(np.float32)
    rewards = rng.normal(size=T).astype(np.float32)
    values = rng.normal(size=T).astype(np.float32)
    dones = np.zeros(T, np.float32)
    bootstrap = np.float32(0.7)

    out = vtrace(
        jnp.asarray(logp), jnp.asarray(logp), jnp.asarray(rewards),
        jnp.asarray(values), jnp.asarray(dones), jnp.asarray(bootstrap),
        gamma=0.99, lam=1.0,
    )
    adv, ret = gae_advantages(
        jnp.asarray(rewards), jnp.asarray(values), jnp.asarray(dones),
        jnp.asarray(bootstrap), gamma=0.99, lam=1.0,
    )
    np.testing.assert_allclose(np.asarray(out.vs), np.asarray(ret), rtol=1e-4)
