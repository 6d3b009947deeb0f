"""GAE / discounted-return scans vs. slow O(T^2) numpy oracles
(SURVEY.md §4.1)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.ops import (
    discounted_returns,
    gae_advantages,
)


def _gae_oracle(rewards, values, dones, last_value, gamma, lam,
                terminations=None, truncation_values=None):
    T = len(rewards)
    values_tp1 = np.concatenate([values[1:], [last_value]])
    cut = dones
    if terminations is not None:
        # a truncated step bootstraps from V(final_obs), a terminal one
        # from nothing; either way the recursion stops at ``dones``
        cut = terminations
        truncated = (dones == 1) & (terminations == 0)
        values_tp1 = np.where(truncated, truncation_values, values_tp1)
    deltas = rewards + gamma * (1 - cut) * values_tp1 - values
    adv = np.zeros(T + 1)
    for t in reversed(range(T)):
        adv[t] = deltas[t] + gamma * lam * (1 - dones[t]) * adv[t + 1]
    return adv[:T], adv[:T] + values


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gae_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    T = 17
    rewards = rng.normal(size=T).astype(np.float32)
    values = rng.normal(size=T).astype(np.float32)
    dones = (rng.random(T) < 0.2).astype(np.float32)
    last_value = np.float32(rng.normal())

    adv, ret = gae_advantages(
        jnp.asarray(rewards),
        jnp.asarray(values),
        jnp.asarray(dones),
        jnp.asarray(last_value),
        gamma=0.99,
        lam=0.95,
    )
    adv_np, ret_np = _gae_oracle(rewards, values, dones, last_value, 0.99, 0.95)
    np.testing.assert_allclose(np.asarray(adv), adv_np, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ret), ret_np, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,B", [(1, 1), (7, 3), (128, 5)])
def test_gae_batched_matches_oracle_columnwise(T, B):
    """``[T, B]`` under ``jax.jit`` with ``terminations`` and
    ``truncation_values`` as ``make_ppo`` passes them: every column is
    the oracle's answer for that env alone."""
    rng = np.random.default_rng(T * 31 + B)
    rewards = rng.normal(size=(T, B)).astype(np.float32)
    values = rng.normal(size=(T, B)).astype(np.float32)
    dones = (rng.random((T, B)) < 0.2).astype(np.float32)
    terms = dones * (rng.random((T, B)) < 0.5).astype(np.float32)
    trunc_v = rng.normal(size=(T, B)).astype(np.float32)
    last_value = rng.normal(size=B).astype(np.float32)

    adv, ret = jax.jit(
        functools.partial(gae_advantages, gamma=0.99, lam=0.95)
    )(
        rewards, values, dones, last_value,
        terminations=terms, truncation_values=trunc_v,
    )
    assert adv.shape == (T, B) and adv.dtype == jnp.float32
    for b in range(B):
        adv_np, ret_np = _gae_oracle(
            rewards[:, b], values[:, b], dones[:, b], last_value[b],
            0.99, 0.95, terms[:, b], trunc_v[:, b],
        )
        np.testing.assert_allclose(adv[:, b], adv_np, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(ret[:, b], ret_np, rtol=1e-4, atol=1e-5)


def test_gae_batched_shapes():
    T, B = 8, 5
    adv, ret = gae_advantages(
        jnp.ones((T, B)),
        jnp.zeros((T, B)),
        jnp.zeros((T, B)),
        jnp.zeros((B,)),
        gamma=0.9,
        lam=1.0,
    )
    assert adv.shape == (T, B) and ret.shape == (T, B)
    # with zero values and no dones, GAE(1) advantage = discounted return
    expected = np.array([(1 - 0.9 ** (T - t)) / (1 - 0.9) for t in range(T)])
    np.testing.assert_allclose(np.asarray(adv[:, 0]), expected, rtol=1e-5)


def test_gae_done_cuts_bootstrap():
    # reward at t=0 with done: advantage must ignore everything after
    adv, _ = gae_advantages(
        jnp.asarray([1.0, 100.0]),
        jnp.asarray([0.0, 0.0]),
        jnp.asarray([1.0, 0.0]),
        jnp.asarray(50.0),
        gamma=0.99,
        lam=0.95,
    )
    np.testing.assert_allclose(float(adv[0]), 1.0, rtol=1e-6)


def test_discounted_returns_oracle():
    rng = np.random.default_rng(3)
    T = 11
    rewards = rng.normal(size=T).astype(np.float32)
    dones = (rng.random(T) < 0.3).astype(np.float32)
    last_value = np.float32(2.0)
    out = discounted_returns(
        jnp.asarray(rewards), jnp.asarray(dones), jnp.asarray(last_value), gamma=0.95
    )
    exp = np.zeros(T + 1)
    exp[T] = last_value
    for t in reversed(range(T)):
        exp[t] = rewards[t] + 0.95 * (1 - dones[t]) * exp[t + 1]
    np.testing.assert_allclose(np.asarray(out), exp[:T], rtol=1e-5, atol=1e-5)


def test_gae_jit_and_grad_safe():
    f = jax.jit(lambda r, v, d, lv: gae_advantages(r, v, d, lv)[0])
    out = f(jnp.ones((4, 2)), jnp.zeros((4, 2)), jnp.zeros((4, 2)), jnp.zeros(2))
    assert out.shape == (4, 2)


def test_gae_time_limit_bootstrap():
    """At a truncated step the target bootstraps from V(final_obs);
    at a terminated step it does not."""
    rewards = jnp.asarray([1.0, 1.0])
    values = jnp.asarray([0.0, 0.0])
    dones = jnp.asarray([1.0, 1.0])      # both steps end an episode
    terms = jnp.asarray([0.0, 1.0])      # step0 truncated, step1 terminal
    trunc_v = jnp.asarray([10.0, 99.0])  # 99 must be ignored (terminal)
    adv, ret = gae_advantages(
        rewards, values, dones, jnp.asarray(0.0),
        gamma=0.5, lam=0.9, terminations=terms, truncation_values=trunc_v,
    )
    # step0: delta = 1 + 0.5*10 - 0 = 6; recursion cut by done -> adv=6
    # step1: delta = 1 (terminal, no bootstrap)
    np.testing.assert_allclose(np.asarray(adv), [6.0, 1.0], rtol=1e-6)

    # without truncation_values, truncation treated as terminal
    adv2, _ = gae_advantages(
        rewards, values, dones, jnp.asarray(0.0),
        gamma=0.5, lam=0.9, terminations=terms,
    )
    np.testing.assert_allclose(np.asarray(adv2), [1.0, 1.0], rtol=1e-6)


def test_gae_accepts_python_scalars():
    adv, ret = gae_advantages([1.0, 1.0], [0.5, 0.5], [0.0, 0.0], 0.25)
    assert adv.shape == (2,)
