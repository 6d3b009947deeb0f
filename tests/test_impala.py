"""IMPALA: queue machinery, learner step, and the in-process
actor/learner topology (SURVEY.md §4.3)."""

import threading
import time

import jax
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.algos import impala
from actor_critic_algs_on_tensorflow_tpu.distributed.queue import (
    TrajectoryQueue,
)
from helpers import greedy_cartpole_return


def _cfg(**kw):
    base = dict(
        env="CartPole-v1",
        num_actors=2,
        envs_per_actor=4,
        rollout_length=8,
        batch_trajectories=2,
        queue_size=4,
        total_env_steps=2 * 4 * 8 * 5,  # 5 learner steps
    )
    base.update(kw)
    return impala.ImpalaConfig(**base)


def test_queue_stats_and_backpressure():
    q = TrajectoryQueue(maxsize=2, watchdog_timeout_s=60)
    q.put(1)
    q.put(2)
    assert q.depth() == 2
    got = [q.get(), q.get()]
    assert got == [1, 2]
    m = q.metrics()
    assert m["queue_puts"] == 2 and m["queue_gets"] == 2
    q.close()


def test_queue_watchdog_flags_starvation():
    q = TrajectoryQueue(maxsize=2, watchdog_timeout_s=0.4)
    time.sleep(1.0)  # nobody produces -> "actors stalled"
    assert any("actors stalled" in a for a in q.watchdog_alerts)
    # Alert counts ride the metrics stream the learner logs.
    assert q.metrics()["queue_watchdog_alerts"] >= 1
    q.close()


def test_queue_close_joins_watchdog_thread():
    q = TrajectoryQueue(maxsize=2, watchdog_timeout_s=0.2)
    watchdog = q._watchdog
    assert watchdog.is_alive()
    q.close()
    assert not watchdog.is_alive(), "close() left the watchdog running"
    # Idempotent.
    q.close()


def test_learner_step_shapes_and_finiteness():
    cfg = _cfg()
    init, learner_step, make_actor, mesh = impala.make_impala(cfg)
    actor_rollout, env_reset = make_actor(0)
    state = init(jax.random.PRNGKey(0))
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    trajs = []
    for i in range(cfg.batch_trajectories):
        env_state, obs, carry, traj, ep = actor_rollout(
            state.params, env_state, obs, carry, jax.random.PRNGKey(i)
        )
        trajs.append(traj)
    batch = impala.stack_trajectories(trajs)
    assert batch.rewards.shape == (
        cfg.rollout_length,
        cfg.batch_trajectories * cfg.envs_per_actor,
    )
    state2, metrics = learner_step(state, batch)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m
    assert int(state2.step) == 1
    # On-policy data => importance ratios == 1.
    np.testing.assert_allclose(m["mean_rho"], 1.0, rtol=1e-5)


def test_run_impala_end_to_end():
    """Async actors + learner drain the step budget; params get published."""
    cfg = _cfg()
    logs = []
    state, history = impala.run_impala(
        cfg, log_interval=1, log_fn=lambda s, m: logs.append((s, m))
    )
    assert int(state.step) == 5
    assert len(history) == 5
    final = history[-1][1]
    assert final["param_version"] >= 1
    assert final["queue_gets"] >= 5 * cfg.batch_trajectories
    assert np.isfinite(final["loss"])
    # All actor/learner threads shut down cleanly.
    assert not any(
        t.name.startswith("impala-actor") and t.is_alive()
        for t in threading.enumerate()
    )


def test_a3c_mode_matches_vtrace_on_policy():
    """With correction="none" the learner runs plain A3C targets; on
    on-policy data (rho == 1) the two modes produce identical losses."""
    cfg_v = _cfg()
    cfg_a = _cfg(correction="none")
    init, step_v, make_actor, _ = impala.make_impala(cfg_v)
    _, step_a, _, _ = impala.make_impala(cfg_a)
    actor_rollout, env_reset = make_actor(0)
    state = init(jax.random.PRNGKey(0))
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    trajs = []
    for i in range(cfg_v.batch_trajectories):
        env_state, obs, carry, traj, _ = actor_rollout(
            state.params, env_state, obs, carry, jax.random.PRNGKey(i)
        )
        trajs.append(traj)
    batch = impala.stack_trajectories(trajs)
    _, m_v = step_v(state, batch)
    _, m_a = step_a(state, batch)
    np.testing.assert_allclose(
        float(m_v["loss"]), float(m_a["loss"]), rtol=1e-5
    )


def test_actor_failure_recovery():
    """An injected actor fault is detected and the actor restarted;
    training still completes the full step budget."""
    cfg = _cfg(max_actor_restarts=2)
    state, history = impala.run_impala(
        cfg, log_interval=1, log_fn=lambda s, m: None, inject_failure_at=1
    )
    assert int(state.step) == 5


def test_actor_failure_exhausts_restart_budget():
    cfg = _cfg(max_actor_restarts=0)
    with pytest.raises(RuntimeError, match="restart budget"):
        impala.run_impala(
            cfg, log_interval=10**9, log_fn=lambda s, m: None,
            inject_failure_at=0,
        )


@pytest.mark.slow
def test_impala_learns_cartpole():
    """Greedy-eval return after training, like the A2C learning test —
    the per-batch ``avg_return`` metric is too sparse to assert on (a
    well-trained policy may finish zero episodes in one 256-step
    learner batch)."""

    cfg = _cfg(
        num_actors=4,
        envs_per_actor=4,
        rollout_length=16,
        batch_trajectories=4,
        total_env_steps=600_000,
        lr=1e-3,
        ent_coef=0.01,
        seed=0,
    )
    state, _ = impala.run_impala(cfg, log_interval=50)
    mean_ret, frac_done = greedy_cartpole_return(state.params)
    assert frac_done == 1.0
    assert mean_ret >= 150.0, mean_ret


@pytest.mark.slow
def test_time_sharded_learner_matches_1d():
    """time_shards=4 learner (2-D data x time mesh, sequence-parallel
    V-trace) must produce the same update as the 1-D learner."""
    import jax.numpy as jnp

    base = dict(rollout_length=16, batch_trajectories=2, envs_per_actor=4)
    cfg1 = _cfg(num_devices=2, **base)
    cfg2 = _cfg(num_devices=8, time_shards=4, **base)  # data=2, time=4

    init1, step1, _, _ = impala.make_impala(cfg1)
    init2, step2, _, _ = impala.make_impala(cfg2)
    state1 = init1(jax.random.PRNGKey(0))
    state2 = init2(jax.random.PRNGKey(0))

    T, B = 16, 8
    key = jax.random.PRNGKey(42)
    ks = jax.random.split(key, 6)
    obs_dim = 4  # CartPole
    batch = impala.ActorTrajectory(
        obs=jax.random.normal(ks[0], (T, B, obs_dim)),
        actions=jax.random.randint(ks[1], (T, B), 0, 2),
        rewards=jax.random.normal(ks[2], (T, B)),
        dones=(jax.random.uniform(ks[3], (T, B)) < 0.1).astype(jnp.float32),
        behaviour_log_probs=-jnp.abs(jax.random.normal(ks[4], (T, B))),
        last_obs=jax.random.normal(ks[5], (B, obs_dim)),
    )

    new1, m1 = step1(state1, batch)
    new2, m2 = step2(state2, batch)
    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(new1.params)),
        jax.tree_util.tree_leaves(jax.device_get(new2.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    for k in m1:
        np.testing.assert_allclose(
            float(m1[k]), float(m2[k]), rtol=1e-5, atol=1e-6, err_msg=k
        )


def test_time_shards_validation():
    with pytest.raises(ValueError, match="rollout_length"):
        impala.make_impala(_cfg(num_devices=8, time_shards=4, rollout_length=6))
    with pytest.raises(ValueError, match="not divisible by time_shards"):
        impala.make_impala(_cfg(num_devices=6, time_shards=4))


def test_impala_continuous_actions_learner_step():
    """Continuous (diagonal-Gaussian) IMPALA: the same async topology
    serves MuJoCo-class control tasks."""
    cfg = impala.ImpalaConfig(
        env="Pendulum-v1",
        num_actors=2,
        envs_per_actor=4,
        rollout_length=8,
        batch_trajectories=2,
        total_env_steps=2 * 4 * 8 * 2,
        num_devices=1,
    )
    init, learner_step, make_actor_programs, _ = impala.make_impala(cfg)
    state = init(jax.random.PRNGKey(0))
    rollout, env_reset = make_actor_programs(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    env_state, obs, carry, traj, _ = rollout(
        state.params, env_state, obs, carry, jax.random.PRNGKey(2)
    )
    assert traj.actions.ndim == 3 and traj.actions.shape[-1] == 1
    assert str(traj.actions.dtype) == "float32"
    batch = impala.stack_trajectories([traj, traj])
    before = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    state, metrics = learner_step(state, batch)
    assert np.isfinite(float(metrics["loss"])), metrics
    after = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    assert not np.allclose(before, after)


@pytest.mark.slow
def test_impala_continuous_end_to_end():
    """run_impala with Gaussian policy on Pendulum: finite losses,
    episodes complete, params move."""
    cfg = impala.ImpalaConfig(
        env="Pendulum-v1",
        num_actors=2,
        envs_per_actor=4,
        rollout_length=16,
        batch_trajectories=2,
        total_env_steps=6_000,
        num_devices=1,
        queue_size=4,
    )
    state, history = impala.run_impala(cfg)
    assert history, "no metrics logged"
    last = history[-1][1]
    assert np.isfinite(last["loss"]), last


def test_impala_normalize_advantages():
    """normalize_advantages standardizes the pg term: the loss stays
    finite and the policy still updates under a 100x reward scale that
    would otherwise dwarf entropy/value terms."""
    base = dict(
        env="CartPole-v1",
        num_actors=1,
        envs_per_actor=4,
        rollout_length=8,
        batch_trajectories=1,
        total_env_steps=64,
        num_devices=1,
    )
    cfg = impala.ImpalaConfig(**base, normalize_advantages=True)
    init, learner_step, make_actor_programs, _ = impala.make_impala(cfg)
    state = init(jax.random.PRNGKey(0))
    rollout, env_reset = make_actor_programs(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    _, _, _, traj, _ = rollout(state.params, env_state, obs, carry, jax.random.PRNGKey(2))
    big = traj.replace(rewards=traj.rewards * 100.0)
    before = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    state, metrics = learner_step(state, impala.stack_trajectories([big]))
    assert np.isfinite(float(metrics["loss"])), metrics
    after = np.asarray(jax.tree_util.tree_leaves(state.params)[0])
    assert not np.allclose(before, after)


# ---- the learner's forward pass on the merged [T * B] batch (PR 30) ----


def _seeded_batch(cfg, obs_shape, obs_dtype, num_actions, seed):
    import jax.numpy as jnp

    T, B = cfg.rollout_length, cfg.batch_trajectories * cfg.envs_per_actor
    k = jax.random.split(jax.random.PRNGKey(seed), 6)

    def obs(key, lead):
        if obs_dtype == jnp.uint8:
            return jax.random.randint(
                key, lead + obs_shape, 0, 256, dtype=jnp.int32
            ).astype(jnp.uint8)
        return jax.random.normal(key, lead + obs_shape, obs_dtype)

    return impala.ActorTrajectory(
        obs=obs(k[0], (T, B)),
        actions=jax.random.randint(k[1], (T, B), 0, num_actions),
        rewards=jax.random.normal(k[2], (T, B)),
        dones=(jax.random.uniform(k[3], (T, B)) < 0.2).astype(jnp.float32),
        behaviour_log_probs=-jax.random.uniform(
            k[4], (T, B), minval=0.5, maxval=2.5
        ),
        last_obs=obs(k[5], (B,)),
    )


def _the_parents_way(cfg, state, batch):
    """Loss, aux terms, the first optimizer step and the V-trace
    targets as the learner computed them before PR 30: the observations
    converted on ``[T, B]`` and the torso flattening its own input."""
    import jax.numpy as jnp
    import optax

    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
    from actor_critic_algs_on_tensorflow_tpu.algos import common
    from actor_critic_algs_on_tensorflow_tpu.ops import (
        entropy_loss,
        value_loss,
        vtrace,
    )

    env, env_params = envs_lib.make(
        cfg.env, num_envs=cfg.envs_per_actor, frame_stack=cfg.frame_stack
    )
    _, dist_and_value = common.make_policy_head(
        env.action_space(env_params), torso=cfg.torso,
        hidden_sizes=cfg.hidden_sizes, compute_dtype=cfg.compute_dtype,
    )
    prep = common.make_obs_prep(cfg.torso, cfg.compute_dtype)
    sg = jax.lax.stop_gradient

    def forward(params):
        dist, values = dist_and_value(params, prep(batch.obs))
        _, last_value = dist_and_value(params, prep(batch.last_obs))
        log_probs = dist.log_prob(batch.actions)
        vt = vtrace(
            batch.behaviour_log_probs, sg(log_probs), batch.rewards,
            sg(values), batch.dones, sg(last_value), gamma=cfg.gamma,
            lam=cfg.vtrace_lam, rho_bar=cfg.rho_bar, c_bar=cfg.c_bar,
        )
        return dist, values, log_probs, vt

    def loss_fn(params):
        dist, values, log_probs, vt = forward(params)
        pg = -jnp.mean(log_probs * sg(vt.pg_advantages))
        vf = value_loss(values, sg(vt.vs))
        ent = dist.entropy().mean()
        total = pg + cfg.vf_coef * vf + cfg.ent_coef * entropy_loss(ent)
        return total, (pg, vf, ent, jnp.mean(vt.rhos))

    def step(state):
        (loss, (pg, vf, ent, rho)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(state.params)
        tx = optax.chain(
            optax.clip_by_global_norm(cfg.max_grad_norm),
            optax.adam(cfg.lr, eps=1e-5),
        )
        _, opt_state = tx.update(grads, state.opt_state, state.params)
        metrics = {
            "loss": loss, "policy_loss": pg, "value_loss": vf,
            "entropy": ent, "mean_rho": rho,
            "grad_norm": optax.global_norm(grads),
        }
        return metrics, opt_state, forward(state.params)[3]

    return jax.jit(step)(state)


@pytest.mark.parametrize(
    "env,torso,dtype,T,B",
    [
        ("PongTPU-v0", "nature_cnn", "bfloat16", 3, 5),
        ("PongTPU-v0", "nature_cnn", "float32", 4, 4),
        ("PongTPU-v0", "nature_cnn", "float32", 3, 5),
        ("CartPole-v1", "mlp", "float32", 8, 6),
    ],
    ids=["frames-bf16-3x5", "frames-f32-4x4", "frames-f32-3x5",
         "vectors-f32-8x6"],
)
def test_learner_forward_on_the_merged_batch_is_the_parents(
    env, torso, dtype, T, B
):
    """The feed-forward learner merges ``[T, B]`` before the torso and
    gives the torso's outputs their axes back (PR 30): same numbers as
    the forward pass over ``[T, B]`` — bit for bit for float32 frames
    — for the loss, its terms, the gradient the first Adam step holds
    (``mu = (1 - b1) * clipped gradient``) and the V-trace targets.
    A square batch would hide values returned in ``[B, T]`` order from
    the shapes; its numbers would not match."""
    import jax.numpy as jnp

    frames = torso == "nature_cnn"
    cfg = impala.ImpalaConfig(
        env=env, torso=torso, compute_dtype=dtype,
        frame_stack=4 if frames else 0,
        num_actors=1, envs_per_actor=B, rollout_length=T,
        batch_trajectories=1, total_env_steps=T * B, num_devices=1,
    )
    progs = impala.make_impala(cfg)
    state = progs.init(jax.random.PRNGKey(3))
    batch = _seeded_batch(
        cfg, (84, 84, 4) if frames else (4,),
        jnp.uint8 if frames else jnp.float32, progs.num_actions, seed=11,
    )
    want_metrics, want_opt, want_vt = _the_parents_way(cfg, state, batch)
    got_vt = progs.vtrace_targets(state.params, batch)
    state1, got_metrics = progs.learner_step(state, batch)

    if frames and dtype == "float32":
        same = np.testing.assert_array_equal
    else:
        # bfloat16, and the float32 MLP: XLA:CPU runs a Dense layer
        # over [T, B, D] and over [T * B, D] as two different dot
        # kernels, equal to rounding (2e-7 here); the convolutions
        # always saw [T * B] frames.
        same = lambda a, b: np.testing.assert_allclose(  # noqa: E731
            a, b, rtol=1e-5, atol=1e-6
        )
    for field in ("vs", "pg_advantages", "rhos"):
        got = np.asarray(getattr(got_vt, field))
        assert got.shape == (T, B), (field, got.shape)
        same(got, np.asarray(getattr(want_vt, field)))
    for key, want in want_metrics.items():
        same(np.asarray(got_metrics[key]), np.asarray(want))
    got_mu = jax.tree_util.tree_leaves(state1.opt_state[1][0].mu)
    want_mu = jax.tree_util.tree_leaves(want_opt[1][0].mu)
    assert len(got_mu) == len(want_mu) > 0
    for got, want in zip(got_mu, want_mu):
        assert np.abs(np.asarray(want)).max() > 0
        same(np.asarray(got), np.asarray(want))
