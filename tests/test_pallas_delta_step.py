"""The one-pass decode step of the gated delta rule
(ops/pallas_delta_step.py) against the plain form it stands in for on
the TPU (models/qwen3_next.py::gated_delta_step).

The suite runs on the CPU mesh, so every call passes ``interpret=True``:
the interpreter is never picked from the backend. The kernel compiled
for the chip at the timed shape is ``tests/test_tpu_hlo.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_delta_step


def _inputs(B, h, dk, dv, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    S = jax.random.normal(ks[0], (B, h, dk, dv))
    q = jax.random.normal(ks[1], (B, h, dk)) * dk ** -0.5
    k = jax.random.normal(ks[2], (B, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[3], (B, h, dv))
    g = -jax.random.uniform(ks[4], (B, h), minval=0.01, maxval=2.0)
    beta = jax.random.uniform(ks[5], (B, h))
    return S, q, k, v, g, beta


def _both(S, q, k, v, g, beta, keep, block_envs):
    want = qn.gated_delta_step(
        S * keep[:, None, None, None], q, k, v, g, beta
    )
    got = pallas_delta_step.gated_delta_step(
        S, q, k, v, g, beta, keep, block_envs=block_envs, interpret=True
    )
    return got, want


@pytest.mark.parametrize("shape,block_envs", [
    # the published head shape: 32 value heads of 128 x 128, an env a block
    ((2, 32, 128, 128), 1),
    # a batch that is no multiple of the block: 3 envs, blocks of 2 asked
    ((3, 4, 16, 128), 2),
    # several envs a block, and widths below the vector unit's tile
    ((4, 3, 8, 8), 2),
])
def test_kernel_equals_the_plain_step(shape, block_envs):
    S, q, k, v, g, beta = _inputs(*shape)
    keep = jnp.ones((shape[0],))
    (S_new, o), (S_want, o_want) = _both(
        S, q, k, v, g, beta, keep, block_envs
    )
    assert S_new.shape == S.shape and o.shape == v.shape
    np.testing.assert_allclose(S_new, S_want, atol=1e-5)
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    # and it is the step, not a copy of its input
    assert float(jnp.max(jnp.abs(S_new - S))) > 0.1


def test_a_reset_env_comes_back_as_a_fresh_start():
    """``keep = 0`` for one env: its new state is ``k delta^T`` of an
    empty state, ``delta = v beta``, whatever the state held, and its
    output is ``(q . k) delta``; the other env is untouched by it."""
    S, q, k, v, g, beta = _inputs(2, 4, 16, 128, seed=1)
    S = S.at[0].set(1e6)
    keep = jnp.array([0.0, 1.0])
    (S_new, o), (S_want, o_want) = _both(S, q, k, v, g, beta, keep, 1)
    delta = v[0] * beta[0][:, None]
    np.testing.assert_allclose(
        S_new[0], k[0][:, :, None] * delta[:, None, :], atol=1e-6
    )
    np.testing.assert_allclose(
        o[0], jnp.sum(q[0] * k[0], -1, keepdims=True) * delta, atol=1e-6
    )
    np.testing.assert_allclose(S_new[1], S_want[1], atol=1e-5)
    np.testing.assert_allclose(o[1], o_want[1], atol=1e-5)


@pytest.mark.parametrize("case", ["beta_0", "g_0"])
def test_the_gates_at_their_ends(case):
    """``beta = 0``: nothing is written, the state only decays and the
    output is ``S^T q`` of the decayed state. ``g = 0``: no decay, the
    pure delta rule."""
    S, q, k, v, g, beta = _inputs(2, 4, 16, 128, seed=2)
    if case == "beta_0":
        beta = jnp.zeros_like(beta)
    else:
        g = jnp.zeros_like(g)
    (S_new, o), (S_want, o_want) = _both(
        S, q, k, v, g, beta, jnp.ones((2,)), 2
    )
    np.testing.assert_allclose(S_new, S_want, atol=1e-5)
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    if case == "beta_0":
        decayed = S * jnp.exp(g)[..., None, None]
        np.testing.assert_allclose(S_new, decayed, atol=1e-6)
        np.testing.assert_allclose(
            o, jnp.sum(decayed * q[..., :, None], -2), atol=1e-5
        )


def test_the_kernel_refuses_a_gradient():
    """The step form is never differentiated by a trainer: asking is a
    mistake, and says where to go instead."""
    S, q, k, v, g, beta = _inputs(2, 2, 8, 8)

    def loss(S):
        _, o = pallas_delta_step.gated_delta_step(
            S, q, k, v, g, beta, jnp.ones((2,)), interpret=True
        )
        return jnp.sum(o)

    with pytest.raises(NotImplementedError, match="chunk_gated_delta_rule"):
        jax.grad(loss)(S)


def test_only_widths_that_tile_the_vector_unit_take_the_kernel():
    assert pallas_delta_step.fits(jnp.zeros((1, 32, 128, 128)))
    assert not pallas_delta_step.fits(jnp.zeros((1, 4, 16, 16)))
