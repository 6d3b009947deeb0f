"""Recurrent (LSTM) policy family: module semantics, PPO/A2C
integration, eval path, and the velocity-masked CartPole POMDP.

The correctness spine is the replay-consistency invariant: the update
replays the collected rollout from the rollout-entry carry, so with
unchanged params the replayed log-probs must reproduce collection's
(PPO ratio == 1 => approx_kl ~ 0, clip_fraction == 0 on the first
update).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.models import RecurrentActorCritic


def _make_model(**kw):
    kw.setdefault("num_actions", 3)
    kw.setdefault("lstm_size", 8)
    kw.setdefault("hidden_sizes", (16,))
    return RecurrentActorCritic(**kw)


@pytest.mark.parametrize(
    "dtype,atol", [(jnp.float32, 1e-6), (jnp.bfloat16, 2e-2)],
    ids=["float32", "bfloat16"],
)
def test_sequence_equals_stepwise(dtype, atol):
    """One [T, B] sequence call == T chained [1, B] calls (the update
    and collection paths share parameters AND function), and the carry
    is float32 on entry and on exit whatever the torso computes in."""
    m = _make_model(dtype=dtype)
    obs = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 6))
    resets = jnp.zeros((5, 4)).at[2, 1].set(1.0).at[3, 0].set(1.0)
    carry = m.initialize_carry(4)
    params = m.init(jax.random.PRNGKey(1), obs, resets, carry)

    logits, values, carry_out = m.apply(params, obs, resets, carry)
    assert logits.shape == (5, 4, 3) and values.shape == (5, 4)

    c = m.initialize_carry(4)
    step_logits, step_values = [], []
    for t in range(5):
        lg, v, c = m.apply(params, obs[t : t + 1], resets[t : t + 1], c)
        step_logits.append(lg[0])
        step_values.append(v[0])
    for leaf in jax.tree_util.tree_leaves((carry, carry_out, c)):
        assert leaf.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(jnp.stack(step_logits)), np.asarray(logits), atol=atol
    )
    np.testing.assert_allclose(
        np.asarray(jnp.stack(step_values)), np.asarray(values), atol=atol
    )
    for stepped, whole in zip(c, carry_out):
        np.testing.assert_allclose(
            np.asarray(stepped), np.asarray(whole), atol=atol
        )


def test_reset_masks_history():
    """A reset at step t makes the suffix identical to a fresh-carry
    rollout of the suffix — no leakage across episode boundaries."""
    m = _make_model()
    obs = jax.random.normal(jax.random.PRNGKey(0), (6, 2, 6))
    carry = m.initialize_carry(2)
    params = m.init(jax.random.PRNGKey(1), obs, jnp.zeros((6, 2)), carry)

    resets = jnp.zeros((6, 2)).at[3, 0].set(1.0)
    logits, _, _ = m.apply(params, obs, resets, carry)
    fresh_logits, _, _ = m.apply(
        params, obs[3:, :1], jnp.zeros((3, 1)), m.initialize_carry(1)
    )
    np.testing.assert_allclose(
        np.asarray(fresh_logits[:, 0]), np.asarray(logits[3:, 0]), atol=1e-6
    )
    # ...and env 1 (no reset) is unaffected by env 0's reset.
    no_reset_logits, _, _ = m.apply(params, obs, jnp.zeros((6, 2)), carry)
    np.testing.assert_allclose(
        np.asarray(no_reset_logits[:, 1]), np.asarray(logits[:, 1]), atol=1e-6
    )


def test_masked_cartpole_obs_hides_velocities():
    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib

    env, params = envs_lib.make("CartPoleMasked-v1", num_envs=3)
    _, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (3, 2)
    assert env.action_space(params).n == 2


def _ppo_cfg(**kw):
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import PPOConfig

    base = dict(
        env="CartPoleMasked-v1",
        num_envs=32,
        rollout_length=16,
        total_env_steps=10_000,
        recurrent=True,
        lstm_size=16,
        hidden_sizes=(32,),
        num_minibatches=1,
        time_limit_bootstrap=False,
    )
    base.update(kw)
    return PPOConfig(**base)


def test_ppo_recurrent_replay_consistency():
    """First update with unchanged params: replayed log-probs match
    collection's, so the PPO ratio is 1 (approx_kl ~ 0, nothing
    clips). This is THE recurrent-replay correctness invariant."""
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    fns = make_ppo(_ppo_cfg(num_epochs=1, num_minibatches=1))
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    assert abs(float(metrics["approx_kl"])) < 1e-6
    assert float(metrics["clip_fraction"]) == 0.0
    assert np.isfinite(float(metrics["loss"]))


def test_ppo_recurrent_env_sliced_minibatches():
    """shuffle='env' keeps whole trajectories per minibatch; the first
    minibatch of epoch 0 still sees unchanged params => its ratio is 1,
    and later minibatches move (params actually update)."""
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    fns = make_ppo(
        _ppo_cfg(num_epochs=2, num_minibatches=4, shuffle="env", lr=1e-2,
                 lr_decay=False)
    )
    state = fns.init(jax.random.PRNGKey(0))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    changed = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), p0, state.params
    )
    assert all(v > 0 for v in jax.tree_util.tree_leaves(changed))


def test_ppo_recurrent_carry_threads_across_iterations():
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    fns = make_ppo(_ppo_cfg(num_epochs=1, num_minibatches=1))
    state = fns.init(jax.random.PRNGKey(0))
    c0 = np.asarray(jax.device_get(state.carry["core"][1]))
    assert (c0 == 0).all()
    state, _ = fns.iteration(state)
    c1 = np.asarray(jax.device_get(state.carry["core"][1]))
    assert np.abs(c1).max() > 0  # the carry advanced with the rollout


@pytest.mark.parametrize(
    "overrides, match",
    [
        (dict(num_minibatches=4, shuffle="full"), "sequence-shaped"),
        (dict(grad_accum=2), "grad_accum"),
        (dict(compact_frames=True, frame_stack=4), "compact_frames"),
        (dict(time_limit_bootstrap=True), "time_limit_bootstrap"),
    ],
)
def test_ppo_recurrent_validation(overrides, match):
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    with pytest.raises(ValueError, match=match):
        make_ppo(_ppo_cfg(**overrides))


def test_recurrent_continuous_rejected():
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    with pytest.raises(ValueError, match="discrete"):
        make_ppo(_ppo_cfg(env="Pendulum-v1"))


def test_a2c_recurrent_runs_and_learns_signal():
    from actor_critic_algs_on_tensorflow_tpu.algos.a2c import (
        A2CConfig,
        make_a2c,
    )

    cfg = A2CConfig(
        env="CartPoleMasked-v1", num_envs=32, rollout_length=16,
        total_env_steps=10_000, recurrent=True, lstm_size=16,
        hidden_sizes=(32,), time_limit_bootstrap=False,
    )
    fns = make_a2c(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    p0 = jax.tree_util.tree_map(lambda x: x.copy(), state.params)
    for _ in range(2):
        state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))
    changed = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), p0, state.params
    )
    assert all(v > 0 for v in jax.tree_util.tree_leaves(changed))


def test_pong_flicker_blanks_frames_but_not_dynamics():
    """PongFlickerTPU: ~flicker_p of observations are blank, and the
    env presents the same task surface as PongTPU (same spaces; the
    dynamics are inherited unchanged — only ``_flicker`` post-processes
    the observation channel)."""
    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib

    fenv, fparams = envs_lib.make("PongFlickerTPU-v0", num_envs=64)
    assert float(fparams.flicker_p) == 0.5
    key = jax.random.PRNGKey(0)
    state, obs = fenv.reset(key, fparams)
    blanks, total = 0, 0
    actions = jnp.zeros((64,), jnp.int32)
    for t in range(20):
        k = jax.random.fold_in(key, t)
        state, obs, rew, done, info = fenv.step(k, state, actions, fparams)
        per_env_blank = (
            np.asarray(obs).reshape(64, -1).max(axis=1) == 0
        )
        blanks += int(per_env_blank.sum())
        total += 64
    assert 0.35 < blanks / total < 0.65  # ~Binomial(1280, 0.5)

    # Same spaces as the base env; dynamics shared by inheritance.
    env, params = envs_lib.make("PongTPU-v0", num_envs=64)
    assert fenv.action_space(fparams).n == env.action_space(params).n
    assert (
        fenv.observation_space(fparams).shape
        == env.observation_space(params).shape
    )


def test_impala_recurrent_replay_consistency():
    """IMPALA-LSTM: the learner replays each trajectory from its ENTRY
    carry. With target params == behaviour params, the replayed
    log-probs equal the actor's, so every V-trace importance ratio is
    exactly 1 (mean_rho == 1) — the async analog of the PPO
    replay-consistency invariant. Also checks LSTM params move."""
    from actor_critic_algs_on_tensorflow_tpu.algos import impala

    cfg = impala.ImpalaConfig(
        env="CartPoleMasked-v1", num_actors=1, envs_per_actor=4,
        rollout_length=8, batch_trajectories=2, total_env_steps=512,
        recurrent=True, lstm_size=16, hidden_sizes=(32,),
        num_devices=1,
    )
    init, learner_step, make_actor, _ = impala.make_impala(cfg)
    state = init(jax.random.PRNGKey(0))
    rollout, env_reset = make_actor(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    trajs = []
    for i in range(cfg.batch_trajectories):
        env_state, obs, carry, traj, _ = rollout(
            state.params, env_state, obs, carry, jax.random.PRNGKey(i)
        )
        trajs.append(traj)
    batch = impala.stack_trajectories(trajs)
    assert batch.entry_lstm[0].shape == (8, 16)  # 2 trajs x 4 envs
    state2, metrics = learner_step(state, batch)
    assert abs(float(metrics["mean_rho"]) - 1.0) < 1e-5
    assert np.isfinite(float(metrics["loss"]))
    changed = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()),
        state.params, state2.params,
    )
    assert all(v > 0 for v in jax.tree_util.tree_leaves(changed))


def test_impala_recurrent_carry_not_reset_between_rollouts():
    """Consecutive rollouts continue the SAME episodes: the second
    trajectory's entry carry is the first's exit state, not zeros."""
    from actor_critic_algs_on_tensorflow_tpu.algos import impala

    cfg = impala.ImpalaConfig(
        env="CartPoleMasked-v1", num_actors=1, envs_per_actor=4,
        rollout_length=8, batch_trajectories=1, total_env_steps=512,
        recurrent=True, lstm_size=16, hidden_sizes=(32,),
        num_devices=1,
    )
    init, _, make_actor, _ = impala.make_impala(cfg)
    state = init(jax.random.PRNGKey(0))
    rollout, env_reset = make_actor(0)
    env_state, obs, carry = env_reset(jax.random.PRNGKey(1))
    env_state, obs, carry, t1, _ = rollout(
        state.params, env_state, obs, carry, jax.random.PRNGKey(2)
    )
    assert np.abs(np.asarray(t1.entry_lstm[0])).max() == 0.0
    _, _, _, t2, _ = rollout(
        state.params, env_state, obs, carry, jax.random.PRNGKey(3)
    )
    np.testing.assert_allclose(
        np.asarray(t2.entry_lstm[1]), np.asarray(carry["core"][1])
    )
    assert np.abs(np.asarray(t2.entry_lstm[1])).max() > 0.0


@pytest.mark.slow
def test_impala_recurrent_end_to_end():
    """Thread-mode async IMPALA-LSTM runs and reports finite metrics."""
    from actor_critic_algs_on_tensorflow_tpu.algos import impala

    cfg = impala.ImpalaConfig(
        env="CartPoleMasked-v1", num_actors=2, envs_per_actor=4,
        rollout_length=8, batch_trajectories=2, total_env_steps=4096,
        recurrent=True, lstm_size=16, hidden_sizes=(32,),
        num_devices=1, queue_size=4,
    )
    state, history = impala.run_impala(cfg, log_interval=4)
    assert int(state.step) == 4096 // (2 * 4 * 8)
    assert history and np.isfinite(history[-1][1]["loss"])


@pytest.mark.slow
def test_cli_recurrent_train_eval_resume_roundtrip(tmp_path, capsys):
    """Recurrent PPO through the full CLI surface: train, checkpoint
    (carry is part of the state pytree), resume, eval (stateful act)."""
    from actor_critic_algs_on_tensorflow_tpu.cli import train as cli

    common = [
        "--algo", "ppo", "--env", "CartPoleMasked-v1",
        "--set", "num_envs=16", "--set", "rollout_length=8",
        "--set", "recurrent=True", "--set", "lstm_size=16",
        "--set", "time_limit_bootstrap=False",
        "--set", "num_minibatches=1", "--set", "num_devices=1",
        "--checkpoint-dir", str(tmp_path / "ck"),
    ]
    assert cli.main(
        common + ["--total-steps", "1024", "--log-interval", "8"]
    ) == 0
    assert cli.main(
        common + ["--total-steps", "2048", "--log-interval", "8",
                  "--resume"]
    ) == 0
    out = capsys.readouterr().out
    assert "resumed from step" in out
    assert cli.main(
        common + ["--eval", "--eval-envs", "8", "--eval-steps", "64"]
    ) == 0
    out = capsys.readouterr().out
    assert "[eval] avg_return=" in out


@pytest.mark.slow
def test_recurrent_ppo_solves_masked_cartpole():
    """The POMDP learning claim: recurrent PPO's GREEDY policy goes far
    beyond the memoryless plateau on velocity-masked CartPole (the
    feedforward policy evals ~40 greedy on this env under the same
    schedule — measured in PERF.md; 300 is unreachable without
    velocity estimation from history)."""
    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib
    from actor_critic_algs_on_tensorflow_tpu.algos import (
        common as acommon,
        evaluation,
    )
    from actor_critic_algs_on_tensorflow_tpu.algos.ppo import make_ppo

    cfg = _ppo_cfg(
        num_envs=8, rollout_length=128, total_env_steps=600_000,
        num_epochs=4, num_minibatches=4, shuffle="env",
        lr=1e-3, lstm_size=128, hidden_sizes=(64,), num_devices=1,
    )
    fns = make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    for _ in range(600_000 // fns.steps_per_iteration):
        state, _ = fns.iteration(state)

    env, env_params = envs_lib.make("CartPoleMasked-v1", num_envs=64)
    act, ast = evaluation._act_fn(
        "ppo", cfg, env.action_space(env_params),
        jax.device_get(state.params), stochastic=False, num_envs=64,
    )
    mean_ret, _, frac = jax.jit(
        lambda k: acommon.evaluate(
            env, env_params, act, k, num_envs=64, max_steps=520,
            act_state=ast,
        )
    )(jax.random.PRNGKey(7))
    assert float(frac) == 1.0
    assert float(mean_ret) >= 300.0, f"greedy masked return {mean_ret}"
