"""Pure-JAX env behavior: CartPole physics vs gymnasium, Pong game
logic, wrapper semantics, scan-compatibility."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu import envs
from actor_critic_algs_on_tensorflow_tpu.envs import (
    AutoReset,
    Box,
    CartPole,
    Discrete,
    EpisodeStats,
    FrameStack,
    PongTPU,
    VecEnv,
)


def test_cartpole_matches_gymnasium_dynamics():
    """Step our CartPole and gymnasium's from the same state with the
    same actions; trajectories must agree to float tolerance."""
    import gymnasium as gym

    genv = gym.make("CartPole-v1").unwrapped
    genv.reset(seed=0)
    start = np.asarray(genv.state, np.float64)

    env = CartPole()
    params = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(0), params)
    state = state.replace(
        x=jnp.float32(start[0]),
        x_dot=jnp.float32(start[1]),
        theta=jnp.float32(start[2]),
        theta_dot=jnp.float32(start[3]),
    )

    rng = np.random.default_rng(1)
    for _ in range(50):
        a = int(rng.integers(0, 2))
        gobs, _, gterm, _, _ = genv.step(a)
        state, obs, _, done, info = env.step(
            jax.random.PRNGKey(0), state, jnp.int32(a), params
        )
        np.testing.assert_allclose(np.asarray(obs), gobs, rtol=2e-4, atol=2e-5)
        assert bool(info["terminated"]) == bool(gterm)
        if gterm:
            break


def test_cartpole_truncates_at_500():
    env = CartPole()
    params = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(3), params)
    state = state.replace(t=jnp.int32(499))
    # hold pole upright-ish so it doesn't terminate
    state = state.replace(
        x=jnp.float32(0.0), x_dot=jnp.float32(0.0),
        theta=jnp.float32(0.0), theta_dot=jnp.float32(0.0),
    )
    _, _, _, done, info = env.step(
        jax.random.PRNGKey(0), state, jnp.int32(0), params
    )
    assert float(done) == 1.0 and float(info["truncated"]) == 1.0


def test_pong_obs_and_scoring():
    env = PongTPU()
    params = env.default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (84, 84, 1) and obs.dtype == jnp.uint8
    # frame contains exactly ball + 2 paddles worth of lit pixels
    lit = int(np.asarray(obs).astype(np.int32).sum() // 255)
    assert lit > 0

    # force a score: ball just left of agent column, moving right, paddle away
    state = state.replace(
        ball_x=jnp.float32(82.5),
        ball_y=jnp.float32(10.0),
        ball_vx=jnp.float32(3.0),
        ball_vy=jnp.float32(0.0),
        agent_y=jnp.float32(70.0),
    )
    _, _, reward, _, _ = env.step(jax.random.PRNGKey(1), state, jnp.int32(0), params)
    assert float(reward) == -1.0

    # force a return: paddle aligned -> ball bounces, no reward
    state2 = state.replace(agent_y=jnp.float32(10.0), ball_x=jnp.float32(80.9))
    ns, _, reward2, _, _ = env.step(
        jax.random.PRNGKey(1), state2, jnp.int32(0), params
    )
    assert float(reward2) == 0.0
    assert float(ns.ball_vx) < 0.0


def test_pong_episode_terminates_at_21():
    env = PongTPU()
    params = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(0), params)
    state = state.replace(
        opp_score=jnp.int32(20),
        ball_x=jnp.float32(82.5),
        ball_y=jnp.float32(10.0),
        ball_vx=jnp.float32(3.0),
        ball_vy=jnp.float32(0.0),
        agent_y=jnp.float32(70.0),
    )
    _, _, r, done, info = env.step(jax.random.PRNGKey(1), state, jnp.int32(0), params)
    assert float(r) == -1.0 and float(done) == 1.0
    assert float(info["terminated"]) == 1.0


def test_frame_stack_rolls_channels():
    env = FrameStack(PongTPU(), 4)
    params = env.default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (84, 84, 4)
    s2, obs2, *_ = env.step(jax.random.PRNGKey(1), state, jnp.int32(2), params)
    np.testing.assert_array_equal(
        np.asarray(obs[..., 1:]), np.asarray(obs2[..., :3])
    )


def test_autoreset_and_episode_stats():
    env = EpisodeStats(AutoReset(CartPole()))
    params = CartPole().default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    # drive it to termination with a constant action
    key = jax.random.PRNGKey(1)
    done_seen = False
    for i in range(200):
        key, sub = jax.random.split(key)
        state, obs, r, done, info = env.step(sub, state, jnp.int32(1), params)
        if float(done) == 1.0:
            done_seen = True
            assert float(info["episode_length"]) == i + 1
            assert float(info["episode_return"]) == i + 1
            # auto-reset: inner step counter is back near zero
            assert int(state.inner.t) == 0
            break
    assert done_seen


def test_vecenv_scan_rollout():
    """The canonical stack must run under lax.scan + jit (Anakin)."""
    env, params = envs.make("CartPole-v1", num_envs=8)
    keys = jax.random.PRNGKey(0)
    state, obs = env.reset(keys, params)
    assert obs.shape == (8, 4)

    def rollout(carry, key):
        state = carry
        actions = jax.random.randint(key, (8,), 0, 2)
        state, obs, r, d, info = env.step(key, state, actions, params)
        return state, (obs, r, d)

    @jax.jit
    def run(state, key):
        keys = jax.random.split(key, 32)
        return jax.lax.scan(rollout, state, keys)

    state, (obs_seq, r_seq, d_seq) = run(state, jax.random.PRNGKey(7))
    assert obs_seq.shape == (32, 8, 4)
    assert float(r_seq.sum()) == 32 * 8  # reward 1 every step


@pytest.mark.parametrize("name", envs.registered_names())
def test_registered_env_anakin_stack(name):
    """EVERY registered pure-JAX env's canonical stack must run under
    jit + lax.scan (the Anakin pattern) — "this env is
    device-residentable" is a pinned property of the registry, not
    folklore (ISSUE 11: the fused IMPALA program compiles any of
    them). Pins: the jitted scan runs, shapes/dtypes are stable, the
    EpisodeStats info leaves the fused program ships are present, and
    every reward is finite."""
    n_envs, length = 4, 8
    env, params = envs.make(name, num_envs=n_envs)
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape[0] == n_envs
    space = env.action_space(params)

    def sample_actions(key):
        if isinstance(space, Discrete):
            return jax.random.randint(key, (n_envs,), 0, space.n)
        if isinstance(space, envs.TokenBlock):
            return jax.vmap(space.sample)(jax.random.split(key, n_envs))
        assert isinstance(space, Box)
        return jax.random.uniform(
            key, (n_envs,) + space.shape,
            minval=space.low, maxval=space.high,
        )

    def _step(carry, key):
        state, obs = carry
        state, obs2, r, d, info = env.step(
            key, state, sample_actions(key), params
        )
        assert obs2.shape == obs.shape and obs2.dtype == obs.dtype
        ep = {
            "episode_return": info["episode_return"],
            "done_episode": info["done_episode"],
        }
        return (state, obs2), (r, d, ep)

    @jax.jit
    def run(state, obs, key):
        return jax.lax.scan(
            _step, (state, obs), jax.random.split(key, length)
        )

    (state, obs), (rews, dones, ep) = run(state, obs, jax.random.PRNGKey(7))
    assert rews.shape == (length, n_envs)
    assert bool(jnp.all(jnp.isfinite(rews)))
    assert ep["episode_return"].shape == (length, n_envs)
    # Same shapes again: the jitted program is reusable (no retrace
    # needed for a second rollout — the fused loop's steady state).
    run(state, obs, jax.random.PRNGKey(8))
    if hasattr(run, "_cache_size"):
        assert run._cache_size() == 1


def test_autoreset_exposes_final_obs():
    """AutoReset must surface the pre-reset observation so time-limit
    bootstrapping can value the truncated state."""
    env = AutoReset(CartPole())
    params = CartPole().default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    # push to termination quickly
    for i in range(100):
        state, obs, r, d, info = env.step(
            jax.random.PRNGKey(i), state, jnp.int32(1), params
        )
        if float(d) == 1.0:
            # returned obs is the NEW episode's obs; final_obs the old one
            assert not np.allclose(np.asarray(obs), np.asarray(info["final_obs"]))
            # terminal state: |x|>2.4 or |theta|>0.2095 in final_obs
            fo = np.asarray(info["final_obs"])
            assert abs(fo[0]) > 2.4 or abs(fo[2]) > 0.2095
            break
    else:
        raise AssertionError("never terminated")


def test_breakout_obs_bricks_and_reward():
    from actor_critic_algs_on_tensorflow_tpu.envs import BreakoutTPU

    env = BreakoutTPU()
    params = env.default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (84, 84, 1) and obs.dtype == jnp.uint8
    assert int(state.lives) == 5
    # full wall renders a solid brick band
    band = np.asarray(obs)[params.brick_top: params.brick_top + 18]
    assert band.sum() > 0

    # force a brick hit: ball flies up INTO the top brick row
    state = state.replace(
        ball_x=jnp.float32(10.0),
        ball_y=jnp.float32(params.brick_top + 4.0),
        ball_vx=jnp.float32(0.0),
        ball_vy=jnp.float32(-1.5),
    )
    ns, nobs, reward, done, _ = env.step(
        jax.random.PRNGKey(1), state, jnp.int32(0), params
    )
    assert float(reward) == 7.0  # top-row Atari value
    assert float(jnp.sum(ns.bricks)) == 71.0  # one of 72 destroyed
    assert float(ns.ball_vy) > 0.0  # bounced
    assert float(done) == 0.0


def test_breakout_life_loss_and_termination():
    from actor_critic_algs_on_tensorflow_tpu.envs import BreakoutTPU

    env = BreakoutTPU()
    params = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(0), params)
    # ball below the paddle heading down, paddle away -> life lost
    state = state.replace(
        ball_x=jnp.float32(10.0),
        ball_y=jnp.float32(82.5),
        ball_vx=jnp.float32(0.0),
        ball_vy=jnp.float32(2.0),
        paddle_x=jnp.float32(70.0),
        lives=jnp.int32(1),
    )
    ns, _, reward, done, info = env.step(
        jax.random.PRNGKey(1), state, jnp.int32(0), params
    )
    assert float(reward) == 0.0
    assert int(ns.lives) == 0
    assert float(done) == 1.0 and float(info["terminated"]) == 1.0


def test_breakout_paddle_bounce_and_rollout():
    from actor_critic_algs_on_tensorflow_tpu.envs import BreakoutTPU
    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib

    env = BreakoutTPU()
    params = env.default_params()
    state, _ = env.reset(jax.random.PRNGKey(0), params)
    state = state.replace(
        ball_x=jnp.float32(40.0),
        ball_y=jnp.float32(80.5),
        ball_vx=jnp.float32(0.0),
        ball_vy=jnp.float32(2.0),
        paddle_x=jnp.float32(40.0),
    )
    ns, _, _, _, _ = env.step(jax.random.PRNGKey(1), state, jnp.int32(0), params)
    assert float(ns.ball_vy) < 0.0  # bounced off the paddle

    # vectorized random rollout through the standard wrapper stack
    venv, vparams = envs_lib.make("BreakoutTPU-v0", num_envs=8, frame_stack=4)
    vstate, vobs = venv.reset(jax.random.PRNGKey(2), vparams)
    assert vobs.shape == (8, 84, 84, 4)

    def _step(carry, key):
        vstate, obs = carry
        actions = jax.random.randint(key, (8,), 0, 4)
        vstate, obs, r, d, info = venv.step(key, vstate, actions, vparams)
        return (vstate, obs), (r, d)

    (_, _), (rews, dones) = jax.lax.scan(
        _step, (vstate, vobs), jax.random.split(jax.random.PRNGKey(3), 200)
    )
    assert bool(jnp.all(jnp.isfinite(rews)))
    assert float(jnp.max(rews)) >= 0.0


def test_reacher_dynamics_and_reward():
    from actor_critic_algs_on_tensorflow_tpu.envs import ReacherTPU
    from actor_critic_algs_on_tensorflow_tpu.envs.reacher import _fingertip

    env = ReacherTPU()
    params = env.default_params()
    state, obs = env.reset(jax.random.PRNGKey(0), params)
    assert obs.shape == (10,)
    # target is reachable
    assert float(jnp.linalg.norm(state.target)) <= params.target_radius + 1e-6
    # obs tail is fingertip-target vector
    np.testing.assert_allclose(
        np.asarray(obs[-2:]),
        np.asarray(_fingertip(state.theta, params) - state.target),
        rtol=1e-5,
    )

    # zero torque from rest: arm stays put, reward = -distance
    state = state.replace(theta_dot=jnp.zeros(2))
    ns, _, reward, done, info = env.step(
        jax.random.PRNGKey(1), state, jnp.zeros(2), params
    )
    dist = float(jnp.linalg.norm(_fingertip(ns.theta, params) - ns.target))
    np.testing.assert_allclose(float(reward), -dist, rtol=1e-5)
    assert float(done) == 0.0

    # torque accelerates the joints; ctrl cost reduces reward
    ns2, _, r2, _, _ = env.step(
        jax.random.PRNGKey(1), state, jnp.ones(2), params
    )
    assert float(jnp.abs(ns2.theta_dot).sum()) > 0.0
    dist2 = float(
        jnp.linalg.norm(_fingertip(ns2.theta, params) - ns2.target)
    )
    np.testing.assert_allclose(
        float(r2), -dist2 - params.ctrl_cost * 2.0, rtol=1e-5
    )

    # 50-step truncation
    state50 = state.replace(t=jnp.int32(49))
    _, _, _, done50, info50 = env.step(
        jax.random.PRNGKey(1), state50, jnp.zeros(2), params
    )
    assert float(done50) == 1.0 and float(info50["truncated"]) == 1.0


def test_reacher_vectorized_rollout():
    from actor_critic_algs_on_tensorflow_tpu import envs as envs_lib

    venv, vparams = envs_lib.make("ReacherTPU-v0", num_envs=8)
    vstate, vobs = venv.reset(jax.random.PRNGKey(0), vparams)
    assert vobs.shape == (8, 10)

    def _step(carry, key):
        vstate, obs = carry
        actions = jax.random.uniform(key, (8, 2), minval=-1.0, maxval=1.0)
        vstate, obs, r, d, info = venv.step(key, vstate, actions, vparams)
        return (vstate, obs), (r, d)

    (_, _), (rews, dones) = jax.lax.scan(
        _step, (vstate, vobs), jax.random.split(jax.random.PRNGKey(1), 120)
    )
    assert bool(jnp.all(jnp.isfinite(rews)))
    assert bool(jnp.all(rews <= 0.0))
    # two truncations per env in 120 steps of 50-step episodes
    assert float(dones.sum(0).min()) >= 2.0


def test_pong_serve_env_reset_mixture():
    """PongServeTPU's resets cover the concession-taxonomy states
    (paddle rows far from center, serves/rallies toward the agent,
    |vy| beyond the standard serve's +-1) while keeping dynamics and
    half its resets identical to PongTPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.envs import PongServeTPU, PongTPU

    env, std = PongServeTPU(), PongTPU()
    params = env.default_params()
    keys = jax.random.split(jax.random.PRNGKey(0), 512)
    states = jax.vmap(lambda k: env.reset(k, params)[0])(keys)
    pads = np.asarray(states.agent_y)
    vys = np.asarray(states.ball_vy)
    vxs = np.asarray(states.ball_vx)
    bxs = np.asarray(states.ball_x)
    # Adversarial serves/rallies put the paddle well outside the
    # standard reset's fixed mid row (42) — including the camped ace
    # rows (~12-18) and the bottom rows the taxonomy names.
    assert pads.min() < 15.0 and pads.max() > 70.0
    assert (pads == params.height / 2.0).mean() > 0.3  # standard anchor
    # Fast diagonals: |vy| beyond the standard serve's +-1 range.
    assert np.abs(vys).max() > 1.5
    # Rally mode: mid-flight right-half balls at super-serve speeds.
    assert (vxs > params.ball_speed + 0.1).any()
    assert bxs.max() > params.width / 2.0 + 5.0
    # All adversarial balls head TOWARD the agent or are standard
    # serves (standard resets may serve either way).
    toward_opp = vxs < 0.0
    assert (bxs[toward_opp] == params.width / 2.0).all()

    # Dynamics are untouched: stepping the same state with the same
    # key/action matches PongTPU bit for bit.
    s0, _ = std.reset(jax.random.PRNGKey(7), params)
    k = jax.random.PRNGKey(8)
    out_a = env.step(k, s0, jnp.int32(3), params)
    out_b = std.step(k, s0, jnp.int32(3), params)
    for a, b in zip(
        jax.tree_util.tree_leaves(out_a), jax.tree_util.tree_leaves(out_b)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# BlockTurns: a step is one pass over a block (envs/block_turns.py)


def _block_turns(turns=3, delay_turns=1):
    from actor_critic_algs_on_tensorflow_tpu.envs.block_turns import (
        BlockTurns,
        BlockTurnsParams,
        _env_block,
    )

    params = BlockTurnsParams(vocab_size=32, block_length=4,
                              denoise_steps=4, turns=turns,
                              delay_turns=delay_turns)
    return BlockTurns(), params, _env_block


def test_block_turns_schedule_is_one_four_one():
    """A turn: the env's clean block, four denoising steps over the
    policy's block (all mask at first), the finished block shown once
    more; 6 x turns steps, then truncated."""
    env, params, env_block = _block_turns()
    mask = params.mask_id
    assert (params.episode_length, params.tokens_per_episode) == (18, 24)
    space = env.action_space(params)
    assert (space.n, space.block_length, space.mask_token_id) == (32, 4, mask)
    key = jax.random.PRNGKey(3)
    state, obs = env.reset(key, params)
    step = jax.jit(lambda s, a: env.step(None, s, a, params))
    seen, dones = [np.asarray(obs)], []
    for t in range(params.episode_length):
        block = np.asarray(obs)
        action = block.copy()
        if (block == mask).any():  # reveal the lowest masked position
            action[np.flatnonzero(block == mask)[0]] = 5
        state, obs, reward, done, info = step(state, jnp.asarray(action))
        seen.append(np.asarray(obs))
        dones.append(float(done))
        assert float(info["terminated"]) == 0.0
        assert float(info["truncated"]) == float(done)
    assert dones == [0.0] * 17 + [1.0]
    for turn in range(params.turns):
        shown = seen[6 * turn: 6 * turn + 6]
        np.testing.assert_array_equal(shown[0], env_block(key, turn, params))
        assert (shown[0] < mask).all()
        masked = [(b == mask).sum() for b in shown]
        assert masked == [0, 4, 3, 2, 1, 0]
        assert shown[5].tolist() == [5, 5, 5, 5]


def test_block_turns_rewards_revealed_ids_that_match_the_delayed_block():
    env, params, env_block = _block_turns(turns=3, delay_turns=1)
    mask = params.mask_id
    key = jax.random.PRNGKey(4)
    state, obs = env.reset(key, params)
    step = jax.jit(lambda s, a: env.step(None, s, a, params))
    rewards = []
    for t in range(params.episode_length):
        turn, phase = divmod(t, 6)
        block = np.asarray(obs)
        action = block.copy()
        target = np.asarray(env_block(key, max(turn - 1, 0), params))
        if phase == 1:    # two right ids at once
            action[:2] = target[:2]
        elif phase == 2:  # a wrong id
            action[2] = (target[2] + 1) % mask
        elif phase == 3:  # an id already shown is not revealed again
            action[0] = (target[0] + 1) % mask
        # phase 4: nothing revealed, so the env fills position 3 with 0
        if phase in (0, 5):
            action = np.full(4, 9)  # ignored
        state, obs, reward, done, _ = step(state, jnp.asarray(action))
        rewards.append(float(reward))
        if phase == 3:
            assert np.asarray(obs)[0] == target[0]  # kept, not overwritten
        if phase == 4:
            assert np.asarray(obs)[3] == 0 and (np.asarray(obs) != mask).all()
    # turn 0 lies before the delay; turns 1 and 2 earn 2 at their first
    # denoising step and nothing else
    assert rewards == [0.0] * 6 + [0.0, 2.0, 0.0, 0.0, 0.0, 0.0] * 2


def test_block_turns_is_a_function_of_its_key():
    env, params, _ = _block_turns()

    def episode(key):
        state, obs = env.reset(key, params)
        out = [obs]
        for _ in range(7):
            state, obs, *_ = env.step(None, state, obs, params)
            out.append(obs)
        return np.stack(out)

    a, b = episode(jax.random.PRNGKey(5)), episode(jax.random.PRNGKey(5))
    np.testing.assert_array_equal(a, b)
    assert (a != episode(jax.random.PRNGKey(6))).any()
