"""The plain references against the program's own model and
operations at a tiny size in float32, where they have to agree
tightly, and the comparison's sharpness: a dropped term, a wrong
coefficient and a half-precision pass are each outside the tolerance."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from actor_critic_algs_on_tensorflow_tpu import ops
from actor_critic_algs_on_tensorflow_tpu.models import DiscreteActorCritic
from perfbench.harness import checks
from perfbench.reference import impala_loss, nature_cnn, ppo_loss
from perfbench.runners import impala as impala_runner, ppo as ppo_runner

T, B, A = 8, 4, 6
HP = {"clip_eps": 0.2, "vf_coef": 0.5, "ent_coef": 0.01}


@pytest.fixture(scope="module")
def setup():
    model = DiscreteActorCritic(num_actions=A, torso="nature_cnn")
    batch = ppo_runner.seeded_batch(
        jax.random.PRNGKey(0), T, B, (84, 84, 4), A
    )
    params = model.init(jax.random.PRNGKey(1), batch["obs"][0])
    return model, params, batch


def test_forward_equals_the_programs_model(setup):
    model, params, batch = setup
    obs = batch["obs"].reshape((T * B, 84, 84, 4))
    logits, value = model.apply(params, obs)
    ref_logits, ref_value = nature_cnn.forward(params, obs)
    np.testing.assert_allclose(ref_logits, logits, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ref_value, value, rtol=1e-4, atol=1e-5)


def test_gae_and_vtrace_equal_the_programs_ops(setup):
    _, _, b = setup
    adv, ret = ops.gae_advantages(
        b["rewards"], b["old_values"], b["dones"], b["last_value"],
        gamma=0.99, lam=0.95,
    )
    r_adv, r_ret = ppo_loss.gae(
        b["rewards"], b["old_values"], b["dones"], b["last_value"],
        0.99, 0.95,
    )
    np.testing.assert_allclose(r_adv, adv, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_ret, ret, rtol=1e-5, atol=1e-6)
    target = b["old_log_probs"] + 0.2
    vt = ops.vtrace(
        b["old_log_probs"], target, b["rewards"], b["old_values"],
        b["dones"], b["last_value"], gamma=0.99,
    )
    vs, pg = impala_loss.vtrace(
        b["old_log_probs"], target, b["rewards"], b["old_values"],
        b["dones"], b["last_value"], 0.99, 1.0, 1.0, 1.0,
    )
    np.testing.assert_allclose(vs, vt.vs, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(pg, vt.pg_advantages, rtol=1e-5, atol=1e-6)


def _flat_batch(b):
    adv, ret = ppo_loss.gae(
        b["rewards"], b["old_values"], b["dones"], b["last_value"],
        0.99, 0.95,
    )
    flat = lambda x: x.reshape((T * B,) + x.shape[2:])
    out = {k: flat(b[k]) for k in
           ("obs", "actions", "old_log_probs", "old_values")}
    out["advantages"], out["returns"] = flat(adv), flat(ret)
    return out


def _ref(params, fb, hp=HP, loss=ppo_loss.loss):
    (total, parts), grads = jax.value_and_grad(loss, has_aux=True)(
        params, fb, hp
    )
    return total, parts, grads


def test_comparison_is_sharp(setup):
    """Against the reference itself the comparison passes exactly; it
    fails for a dropped entropy term, a doubled value coefficient, and
    weights rounded to an 8-bit float (the step below bfloat16)."""
    _, params, batch = setup
    fb = _flat_batch(batch)
    total, parts, grads = _ref(params, fb)
    scale = checks.loss_scale(parts, HP)
    same = checks.compare_loss_and_grads(total, total, scale, grads, grads)
    assert same["ok"] and same["grad_cosine"] == pytest.approx(1.0)

    t2, _, g2 = _ref(params, fb, dict(HP, ent_coef=0.0))
    assert not checks.compare_loss_and_grads(t2, total, scale, g2, grads)["ok"]
    t3, _, g3 = _ref(params, fb, dict(HP, vf_coef=1.0))
    assert not checks.compare_loss_and_grads(t3, total, scale, g3, grads)["ok"]

    def half_loss(p, b, hp):
        p16 = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), p
        )
        return ppo_loss.loss(p16, b, hp)

    t4, _, g4 = _ref(params, fb, loss=half_loss)
    assert not checks.compare_loss_and_grads(t4, total, scale, g4, grads)["ok"]


def test_adam_state_gives_back_the_first_gradient():
    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(3)}
    grads = {"w": jnp.full((2, 3), 30.0), "b": jnp.array([1.0, -2.0, 3.0])}
    tx = optax.chain(optax.clip_by_global_norm(40.0),
                     optax.adam(1e-3, eps=1e-5))
    _, state = tx.update(grads, tx.init(params), params)
    norm = float(optax.global_norm(grads))
    assert norm > 40.0      # the clip is active
    back = checks.adam_first_step_grads(state, norm, 40.0)
    for k in grads:
        np.testing.assert_allclose(back[k], grads[k], rtol=1e-5)
    assert checks.optimizer_count(state) == 1


def test_impala_reference_chunks_average_to_the_batch(setup):
    _, params, _ = setup
    b = impala_runner.seeded_batch(
        jax.random.PRNGKey(2), T, B, (84, 84, 4), A
    )
    hp = {"gamma": 0.99, "vtrace_lam": 1.0, "rho_bar": 1.0, "c_bar": 1.0,
          "vf_coef": 0.5, "ent_coef": 0.01}
    whole, _ = impala_loss.loss(params, b, hp)
    halves = []
    for s in (slice(0, 2), slice(2, 4)):
        chunk = {k: (v[s] if k == "last_obs" else v[:, s])
                 for k, v in b.items()}
        halves.append(impala_loss.loss(params, chunk, hp)[0])
    assert float(whole) == pytest.approx(float(sum(halves) / 2), rel=1e-5)
