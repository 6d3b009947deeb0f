"""Native C++ env pool: build, contract, physics parity, trainer smoke."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib


def test_native_pool_builds_and_steps():
    env, params = envs_lib.make("native:CartPole-v1", num_envs=4)
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (4, 4) and obs.dtype == jnp.float32
    # Fresh CartPole resets are within +-0.05 on every dim.
    assert float(jnp.max(jnp.abs(obs))) <= 0.05
    state, obs, r, d, info = env.step(
        jax.random.PRNGKey(1), state, jnp.ones((4,)), params
    )
    np.testing.assert_array_equal(np.asarray(r), 1.0)
    for k in ("terminated", "truncated", "final_obs", "episode_return",
              "episode_length", "done_episode"):
        assert k in info


def test_native_cartpole_physics_matches_pure_jax():
    """Same state + same action => same next state as the pure-JAX env
    (both implement gymnasium's closed-form Euler dynamics)."""
    from actor_critic_algs_on_tensorflow_tpu.envs.cartpole import CartPole

    native, _ = envs_lib.make("native:CartPole-v1", num_envs=1, fresh=True)
    nstate, nobs = native.reset(jax.random.PRNGKey(7), None)

    jenv = CartPole()
    jparams = jenv.default_params()
    jstate, _ = jenv.reset(jax.random.PRNGKey(0), jparams)
    # Force the pure-JAX env into the native pool's start state.
    x, xd, th, thd = [float(v) for v in np.asarray(nobs[0])]
    jstate = jstate.replace(x=jnp.asarray(x), x_dot=jnp.asarray(xd),
                            theta=jnp.asarray(th), theta_dot=jnp.asarray(thd))

    for t in range(20):
        a = t % 2
        nstate, nobs, nr, nd, _ = native.step(
            jax.random.PRNGKey(t), nstate, jnp.asarray([a], jnp.float32), None
        )
        jstate, jobs, jr, jd, _ = jenv.step(
            jax.random.PRNGKey(t), jstate, jnp.asarray(a), jparams
        )
        np.testing.assert_allclose(
            np.asarray(nobs[0]), np.asarray(jobs), rtol=1e-5, atol=1e-6,
            err_msg=f"diverged at step {t}",
        )
        assert float(nd[0]) == float(jd)
        if float(nd[0]) > 0.5:
            break


def test_native_episode_accounting_and_autoreset():
    env, _ = envs_lib.make("native:CartPole-v1", num_envs=2, fresh=True)
    state, obs = env.reset(jax.random.PRNGKey(0), None)
    done_seen = False
    for i in range(300):
        state, obs, r, d, info = env.step(
            jax.random.PRNGKey(0), state, jnp.zeros((2,)), None
        )
        if float(jnp.max(d)) > 0.5:
            done_seen = True
            i_env = int(jnp.argmax(d))
            # Episode stats cover the finished episode at the done step.
            assert float(info["episode_return"][i_env]) >= 1.0
            # obs already belongs to the new episode (SAME_STEP reset).
            assert float(jnp.max(jnp.abs(obs[i_env]))) <= 0.05
            # final_obs is the pre-reset state (out of start-state range
            # for a termination at the +-12deg/2.4 bound).
            break
    assert done_seen


def test_native_env_inside_jitted_scan():
    env, _ = envs_lib.make("native:Pendulum-v1", num_envs=3, fresh=True)

    @jax.jit
    def roll(key):
        state, obs = env.reset(key, None)

        def step(c, k):
            state, obs = c
            a = jax.random.uniform(k, (3, 1), minval=-2.0, maxval=2.0)
            state, obs, r, d, info = env.step(k, state, a, None)
            return (state, obs), r

        (state, obs), rs = jax.lax.scan(
            step, (state, obs), jax.random.split(key, 30)
        )
        return rs

    rs = roll(jax.random.PRNGKey(0))
    assert rs.shape == (30, 3)
    assert float(jnp.max(rs)) <= 0.0  # pendulum rewards are non-positive


@pytest.mark.slow
def test_a2c_trains_on_native_env():
    from actor_critic_algs_on_tensorflow_tpu.algos import a2c

    cfg = a2c.A2CConfig(
        env="native:CartPole-v1", num_envs=8, rollout_length=8,
        num_devices=1,
    )
    fns = a2c.make_a2c(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    for _ in range(3):
        state, metrics = fns.iteration(state)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m


def test_library_is_keyed_by_source_content_not_mtime(tmp_path, monkeypatch):
    """A checkout or copy gives every file a fresh mtime; what decides
    a rebuild is the CONTENT of envpool.cpp."""
    import os
    import shutil

    from actor_critic_algs_on_tensorflow_tpu.envs import native

    built = native._lib_path()
    copy = tmp_path / "envpool.cpp"
    shutil.copy(native._SRC, copy)
    os.utime(copy, (1, 1))  # same content, ancient mtime
    monkeypatch.setattr(native, "_SRC", str(copy))
    assert native._lib_path() == built
    copy.write_text(copy.read_text() + "\n// edited\n")
    assert native._lib_path() != built


def test_library_load_error_is_raised_not_rebuilt(tmp_path, monkeypatch):
    from actor_critic_algs_on_tensorflow_tpu.envs import native

    broken = tmp_path / "libenvpool-broken.so"
    broken.write_bytes(b"not a shared object")
    compiled = []
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_lib_path", lambda: str(broken))
    monkeypatch.setattr(native, "_compile", compiled.append)
    with pytest.raises(OSError):
        native._load_library()
    assert compiled == []
