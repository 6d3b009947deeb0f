"""PPO end-to-end: smoke, determinism, minibatch equivalence, and the
CartPole learning test (SURVEY.md §4.2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.algos import common, ppo
from helpers import greedy_cartpole_return


def _params_l2(tree):
    return float(
        sum(jnp.sum(x**2) for x in jax.tree_util.tree_leaves(tree))
    )


def test_ppo_iteration_smoke():
    cfg = ppo.PPOConfig(num_envs=8, rollout_length=16)
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    before = _params_l2(state.params)
    state, metrics = fns.iteration(state)
    after = _params_l2(state.params)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m
    assert after != before
    assert int(state.step) == 1
    # First epoch's first minibatch is on-policy: ratio == 1, so the
    # averaged clip_fraction must be < 1 and approx_kl small-ish.
    assert 0.0 <= m["clip_fraction"] < 1.0


def test_ppo_continuous_smoke():
    cfg = ppo.PPOConfig(
        env="Pendulum-v1", num_envs=8, rollout_length=16, normalize_adv=True
    )
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m


def test_ppo_determinism():
    cfg = ppo.PPOConfig(num_envs=8, rollout_length=16)
    fns = ppo.make_ppo(cfg)

    def run(seed):
        state = fns.init(jax.random.PRNGKey(seed))
        out = []
        for _ in range(2):
            state, metrics = fns.iteration(state)
            jax.block_until_ready(metrics)
            out.append(float(metrics["loss"]))
        return out

    assert run(0) == run(0)
    assert run(0) != run(1)


def test_ppo_nature_cnn_smoke():
    """PongTPU-v0 with the Nature-CNN torso compiles and runs one
    iteration (the headline workload's network, BASELINE.json:8)."""
    cfg = ppo.PPOConfig(
        env="PongTPU-v0",
        num_envs=8,
        rollout_length=8,
        frame_stack=4,
        torso="nature_cnn",
        num_minibatches=2,
        num_epochs=2,
        time_limit_bootstrap=False,
    )
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m


@pytest.mark.slow
def test_ppo_solves_cartpole():
    cfg = ppo.PPOConfig(
        num_envs=8,
        rollout_length=128,
        total_env_steps=150_000,
        lr=2.5e-4,
        seed=0,
    )
    fns = ppo.make_ppo(cfg)
    state, _ = common.run_loop(
        fns,
        total_env_steps=cfg.total_env_steps,
        seed=0,
        log_interval_iters=10**9,
    )

    mean_ret, frac_done = greedy_cartpole_return(state.params)
    assert frac_done == 1.0
    assert mean_ret >= 195.0, mean_ret


@pytest.mark.slow
def test_ppo_continuous_pendulum_smoke():
    """Continuous-control PPO path (DiagGaussian policy)."""
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.algos import ppo

    cfg = ppo.PPOConfig(
        env="Pendulum-v1", num_envs=16, rollout_length=8,
        num_epochs=2, num_minibatches=2,
    )
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    state, metrics = fns.iteration(state)
    m = {k: float(v) for k, v in metrics.items()}
    assert np.isfinite(list(m.values())).all(), m


def test_ppo_bfloat16_compute():
    """bf16 torso compute keeps f32 params and finite f32 outputs."""
    import numpy as np

    from actor_critic_algs_on_tensorflow_tpu.algos import ppo

    cfg = ppo.PPOConfig(
        num_envs=16, rollout_length=8, num_epochs=1, num_minibatches=2,
        compute_dtype="bfloat16",
    )
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(state.params)
    assert all(x.dtype == jnp.float32 for x in leaves)
    state, metrics = fns.iteration(state)
    assert np.isfinite(float(metrics["loss"]))


def test_ppo_whole_batch_epoch_on_policy_alignment():
    # num_minibatches=1 takes the gather-free whole-batch path. With a
    # single epoch the one update is exactly on-policy: recomputed
    # log-probs must equal the rollout's stored log-probs, so ratio==1,
    # clip_fraction==0, approx_kl~~0. Any misalignment between obs_flat
    # and the flattened batch fields (the invariant the gather used to
    # enforce by construction) breaks this immediately.
    cfg = ppo.PPOConfig(
        num_envs=8, rollout_length=16, num_epochs=1, num_minibatches=1
    )
    fns = ppo.make_ppo(cfg)
    state = fns.init(jax.random.PRNGKey(0))
    before = _params_l2(state.params)  # read BEFORE donation
    state1, m1 = fns.iteration(state)
    vals = {k: float(v) for k, v in m1.items()}
    assert np.isfinite(list(vals.values())).all(), vals
    assert vals["clip_fraction"] == 0.0, vals
    assert abs(vals["approx_kl"]) < 1e-5, vals
    assert _params_l2(state1.params) != before


@pytest.mark.parametrize("compact", [False, True], ids=["full", "compact"])
def test_ppo_grad_accum_matches_whole_batch(compact):
    # Contiguous-slice gradient accumulation is mathematically the
    # whole-batch gradient (full-batch advantage normalization, equal
    # slice sizes, one optimizer step per epoch): the same seed must
    # produce near-identical params and metrics with grad_accum 1 vs 4.
    kw = dict(
        env="PongTPU-v0",
        num_envs=8,
        rollout_length=16,
        frame_stack=4,
        torso="nature_cnn",
        num_epochs=2,
        num_minibatches=1,
        time_limit_bootstrap=False,
        compact_frames=compact,
    )
    whole = ppo.make_ppo(ppo.PPOConfig(**kw))
    accum = ppo.make_ppo(ppo.PPOConfig(**kw, grad_accum=4))

    s_w = whole.init(jax.random.PRNGKey(3))
    s_a = accum.init(jax.random.PRNGKey(3))
    for _ in range(2):
        s_w, m_w = whole.iteration(s_w)
        s_a, m_a = accum.iteration(s_a)
    jax.block_until_ready((s_w, s_a))
    for k in m_w:
        np.testing.assert_allclose(
            float(m_w[k]), float(m_a[k]), rtol=2e-4, atol=2e-5, err_msg=k
        )
    flat_w = jax.tree_util.tree_leaves(s_w.params)
    flat_a = jax.tree_util.tree_leaves(s_a.params)
    for w, a in zip(flat_w, flat_a):
        np.testing.assert_allclose(
            np.asarray(w), np.asarray(a), rtol=1e-4, atol=1e-5
        )


def test_ppo_grad_accum_validation():
    with pytest.raises(ValueError, match="num_minibatches=1"):
        ppo.make_ppo(
            ppo.PPOConfig(num_envs=8, num_minibatches=4, grad_accum=2)
        )
    with pytest.raises(ValueError, match="not divisible"):
        ppo.make_ppo(
            ppo.PPOConfig(
                num_envs=8, rollout_length=10,
                num_minibatches=1, grad_accum=3,
            )
        )


def test_env_block_starts_is_a_permuted_partition():
    from actor_critic_algs_on_tensorflow_tpu.data.rollout import (
        env_block_starts,
    )

    starts = env_block_starts(jax.random.PRNGKey(0), 4, 16)
    assert sorted(np.asarray(starts).tolist()) == [0, 16, 32, 48]
    orders = {
        tuple(np.asarray(env_block_starts(jax.random.PRNGKey(k), 4, 16)))
        for k in range(8)
    }
    assert len(orders) > 1  # the visit order really is drawn per key


@pytest.mark.parametrize("num_minibatches", [2, 4, 16])
@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
@pytest.mark.parametrize("rank", [2, 5])
def test_env_blocks_equal_the_per_minibatch_slice(rank, dtype, num_minibatches):
    # Block i of the block-major arrangement is what the minibatch loop
    # used to cut for itself: x[:, i*mb:(i+1)*mb] merged t-major.
    from actor_critic_algs_on_tensorflow_tpu.data.rollout import env_blocks

    t, b = 6, 32
    shape = (t, b) + (5, 3, 4)[: rank - 2]
    x = jnp.asarray(
        np.random.default_rng(rank + num_minibatches)
        .integers(0, 255, shape).astype(dtype)
    )
    mb = b // num_minibatches
    blocks = env_blocks(x, num_minibatches)
    assert blocks.dtype == x.dtype
    assert blocks.shape == (num_minibatches, t * mb) + shape[2:]
    for i in range(num_minibatches):
        want = jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=1).reshape(
            (t * mb,) + shape[2:]
        )
        np.testing.assert_array_equal(np.asarray(blocks[i]), np.asarray(want))


def test_ppo_shuffle_env_smoke_and_determinism():
    cfg = ppo.PPOConfig(
        num_envs=8, rollout_length=16, num_minibatches=4, shuffle="env",
        num_devices=1,
    )
    fns = ppo.make_ppo(cfg)

    def run(seed):
        state = fns.init(jax.random.PRNGKey(seed))
        out = []
        for _ in range(2):
            state, metrics = fns.iteration(state)
            jax.block_until_ready(metrics)
            out.append(float(metrics["loss"]))
        m = {k: float(v) for k, v in metrics.items()}
        assert np.isfinite(list(m.values())).all(), m
        return out

    assert run(0) == run(0)
    assert run(0) != run(1)


@pytest.mark.parametrize("num_epochs,num_minibatches", [(2, 4), (3, 2)])
def test_ppo_shuffle_env_compact_frames_matches_full_storage(
    num_epochs, num_minibatches
):
    # The compact-frames leg of shuffle="env" rebuilds minibatch obs by
    # flat index (t*B + env); compact storage is exact, so the same
    # seed must produce identical params with and without it. The full
    # leg indexes the block-major arrangement (data.rollout.env_blocks)
    # from the same env_block_starts draw: a block taken out of order in
    # any epoch shows as different params (2 blocks over 3 epochs too).
    kw = dict(
        env="PongTPU-v0",
        num_envs=8,
        rollout_length=16,
        frame_stack=4,
        torso="nature_cnn",
        num_epochs=num_epochs,
        num_minibatches=num_minibatches,
        shuffle="env",
        time_limit_bootstrap=False,
        num_devices=1,
    )
    full = ppo.make_ppo(ppo.PPOConfig(**kw))
    compact = ppo.make_ppo(ppo.PPOConfig(**kw, compact_frames=True))
    s_f = full.init(jax.random.PRNGKey(3))
    s_c = compact.init(jax.random.PRNGKey(3))
    for _ in range(2):
        s_f, m_f = full.iteration(s_f)
        s_c, m_c = compact.iteration(s_c)
    jax.block_until_ready((s_f, s_c))
    for k in m_f:
        np.testing.assert_allclose(
            float(m_f[k]), float(m_c[k]), rtol=2e-4, atol=2e-5, err_msg=k
        )
    for f, c in zip(
        jax.tree_util.tree_leaves(s_f.params),
        jax.tree_util.tree_leaves(s_c.params),
    ):
        np.testing.assert_allclose(
            np.asarray(f), np.asarray(c), rtol=1e-4, atol=1e-5
        )


def test_ppo_shuffle_env_validation():
    with pytest.raises(ValueError, match="shuffle"):
        ppo.make_ppo(
            ppo.PPOConfig(num_envs=8, shuffle="banana", num_devices=1)
        )
    with pytest.raises(ValueError, match="env axis"):
        ppo.make_ppo(
            ppo.PPOConfig(
                num_envs=8, rollout_length=12,
                num_minibatches=3, shuffle="env", num_devices=1,
            )
        )


@pytest.mark.slow
def test_ppo_shuffle_env_solves_cartpole():
    cfg = ppo.PPOConfig(
        num_envs=8,
        rollout_length=128,
        total_env_steps=150_000,
        lr=2.5e-4,
        num_minibatches=4,
        shuffle="env",
        num_devices=1,
        seed=0,
    )
    fns = ppo.make_ppo(cfg)
    state, _ = common.run_loop(
        fns,
        total_env_steps=cfg.total_env_steps,
        seed=0,
        log_interval_iters=10**9,
    )
    mean_ret, frac_done = greedy_cartpole_return(state.params)
    assert frac_done == 1.0
    assert mean_ret >= 195.0, mean_ret
