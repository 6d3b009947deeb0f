"""Pallas backward-recurrence kernel vs the lax.scan reference paths.

The suite runs on the CPU mesh, so every call passes ``interpret=True``
(ops) or ``use_pallas="interpret"`` (trainers): the interpreter is never
picked from the backend. The compiled kernel is ``chip_smoke.py``'s
leg D."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.ops import gae_advantages, vtrace
from actor_critic_algs_on_tensorflow_tpu.ops.pallas_scan import (
    linear_backward_scan,
)


def _rand(key, *shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape)


def test_linear_backward_scan_matches_numpy_oracle():
    T, B = 13, 37  # deliberately unaligned with (8, 128) tiles
    deltas = np.asarray(_rand(0, T, B))
    decay = np.abs(np.asarray(_rand(1, T, B))) * 0.9
    out = linear_backward_scan(
        jnp.asarray(deltas), jnp.asarray(decay), interpret=True
    )
    acc = np.zeros(B)
    expect = np.zeros((T, B))
    for t in range(T - 1, -1, -1):
        acc = deltas[t] + decay[t] * acc
        expect[t] = acc
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_linear_backward_scan_with_init():
    T, B = 5, 3
    deltas = np.asarray(_rand(2, T, B))
    decay = np.full((T, B), 0.5)
    init = np.asarray(_rand(3, B))
    out = linear_backward_scan(
        jnp.asarray(deltas), jnp.asarray(decay), jnp.asarray(init),
        interpret=True,
    )
    acc = init.copy()
    expect = np.zeros((T, B))
    for t in range(T - 1, -1, -1):
        acc = deltas[t] + decay[t] * acc
        expect[t] = acc
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-6)


def test_gae_pallas_path_matches_scan_path():
    T, B = 16, 24
    rewards, values = _rand(4, T, B), _rand(5, T, B)
    dones = (jax.random.uniform(jax.random.PRNGKey(6), (T, B)) < 0.1).astype(
        jnp.float32
    )
    last_value = _rand(7, B)
    a0, r0 = gae_advantages(rewards, values, dones, last_value)
    a1, r1 = gae_advantages(
        rewards, values, dones, last_value, use_pallas="interpret"
    )
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(r0), np.asarray(r1), rtol=1e-5, atol=1e-6)


def test_vtrace_pallas_path_matches_scan_path():
    T, B = 12, 9
    b_lp, t_lp = _rand(8, T, B) * 0.1, _rand(9, T, B) * 0.1
    rewards, values = _rand(10, T, B), _rand(11, T, B)
    dones = (jax.random.uniform(jax.random.PRNGKey(12), (T, B)) < 0.1).astype(
        jnp.float32
    )
    bootstrap = _rand(13, B)
    v0 = vtrace(b_lp, t_lp, rewards, values, dones, bootstrap)
    v1 = vtrace(
        b_lp, t_lp, rewards, values, dones, bootstrap, use_pallas="interpret"
    )
    np.testing.assert_allclose(
        np.asarray(v0.vs), np.asarray(v1.vs), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(v0.pg_advantages), np.asarray(v1.pg_advantages),
        rtol=1e-5, atol=1e-6,
    )


def test_pallas_scan_composes_with_jit():
    """Trainers call the kernel on stop-gradient'd inputs inside jit;
    ensure that composition works."""

    @jax.jit
    def f(deltas, decay):
        return linear_backward_scan(deltas, decay, interpret=True).sum()

    out = f(_rand(14, 8, 4), jnp.full((8, 4), 0.9))
    assert np.isfinite(float(out))


def test_trainer_configs_reach_pallas_path():
    """use_pallas_scan is wired from configs into the ops."""
    import numpy as np
    from actor_critic_algs_on_tensorflow_tpu.algos import a2c

    cfg = a2c.A2CConfig(
        num_envs=16, rollout_length=8, use_pallas_scan="interpret"
    )
    fns = a2c.make_a2c(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))


def test_compiled_kernel_is_an_error_off_tpu():
    """With ``interpret`` unset the kernel compiles for the TPU; on the
    CPU that raises instead of detouring through the interpreter."""
    assert jax.default_backend() == "cpu"
    with pytest.raises(ValueError, match="interpret mode"):
        jax.block_until_ready(
            linear_backward_scan(_rand(15, 8, 4), jnp.full((8, 4), 0.9))
        )
