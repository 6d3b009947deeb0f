"""Network shapes, dtypes, and parameter counts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.models import (
    DeterministicActor,
    DiscreteActorCritic,
    GaussianActorCritic,
    NatureCNN,
    RecurrentActorCritic,
    SquashedGaussianActor,
    TwinQCritic,
)


def test_mlp_actor_critic_shapes():
    model = DiscreteActorCritic(num_actions=2)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((3, 4)))
    logits, value = model.apply(params, jnp.zeros((3, 4)))
    assert logits.shape == (3, 2) and value.shape == (3,)
    assert logits.dtype == jnp.float32


def test_nature_cnn_output_and_param_count():
    model = NatureCNN()
    x = jnp.zeros((2, 84, 84, 4), jnp.uint8)
    params = model.init(jax.random.PRNGKey(0), x)
    out = model.apply(params, x)
    assert out.shape == (2, 512)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    # canonical Nature-DQN torso: conv stack + 3136->512 dense ~ 1.68M
    assert 1_600_000 < n_params < 1_800_000


def test_nature_cnn_handles_time_batch_axes():
    model = DiscreteActorCritic(num_actions=6, torso="nature_cnn")
    x = jnp.zeros((5, 3, 84, 84, 4), jnp.uint8)  # [T, B, H, W, C]
    params = model.init(jax.random.PRNGKey(0), x)
    logits, value = model.apply(params, x)
    assert logits.shape == (5, 3, 6) and value.shape == (5, 3)


def test_gaussian_actor_critic():
    model = GaussianActorCritic(action_dim=6)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 17)))
    mean, log_std, value = model.apply(params, jnp.zeros((4, 17)))
    assert mean.shape == (4, 6) and log_std.shape == (4, 6) and value.shape == (4,)


def test_ddpg_heads():
    actor = DeterministicActor(action_dim=6)
    ap = actor.init(jax.random.PRNGKey(0), jnp.zeros((2, 17)))
    a = actor.apply(ap, jnp.zeros((2, 17)))
    assert a.shape == (2, 6)
    assert np.all(np.abs(np.asarray(a)) <= 1.0)

    obs = jax.random.normal(jax.random.PRNGKey(2), (2, 17))
    critic = TwinQCritic()
    cp = critic.init(jax.random.PRNGKey(1), obs, a)
    q1, q2 = critic.apply(cp, obs, a)
    assert q1.shape == (2,) and q2.shape == (2,)
    # twin networks must be independently initialized
    assert not np.allclose(np.asarray(q1), np.asarray(q2))


def test_sac_actor_bounds():
    actor = SquashedGaussianActor(action_dim=17)
    p = actor.init(jax.random.PRNGKey(0), jnp.zeros((3, 376)))
    mean, log_std = actor.apply(p, jnp.zeros((3, 376)))
    assert mean.shape == (3, 17)
    assert np.all(np.asarray(log_std) >= -20.0) and np.all(
        np.asarray(log_std) <= 2.0
    )


@pytest.mark.parametrize("torso", ["nature_cnn_s2d", "resnet_deep"])
@pytest.mark.parametrize("recurrent", [False, True], ids=["ff", "lstm"])
def test_unknown_torso_is_refused_by_name(torso, recurrent):
    """A torso name the module does not build must not fall through to
    the MLP (which would train an MLP on pixels, in silence)."""
    obs = jnp.zeros((2, 3, 84, 84, 4), jnp.uint8)
    if recurrent:
        model = RecurrentActorCritic(num_actions=6, torso=torso)
        args = (obs, jnp.zeros((2, 3)), model.initialize_carry(3))
    else:
        model = DiscreteActorCritic(num_actions=6, torso=torso)
        args = (obs,)
    with pytest.raises(ValueError, match=f"unknown torso '{torso}'.*'mlp'"):
        model.init(jax.random.PRNGKey(0), *args)
