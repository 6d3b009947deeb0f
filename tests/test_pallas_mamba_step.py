"""The one-pass decode step of the Mamba-2 recurrence
(ops/pallas_mamba_step.py) against the plain form it stands in for on
the TPU (models/granite_hybrid.py::mamba_step), in place in the one
array that holds every layer's state.

The suite runs on the CPU mesh, so every call passes ``interpret=True``:
the interpreter is never picked from the backend. The kernel compiled
for the chip at the timed shape is ``tests/test_tpu_hlo.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
from actor_critic_algs_on_tensorflow_tpu.models import granite_hybrid as gh
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_mamba_step


def _inputs(b, layers, h, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    state = jax.random.normal(ks[0], (b, layers, h, p, n))
    x = jax.random.normal(ks[1], (b, h, p))
    dt = jax.random.uniform(ks[2], (b, h), minval=1e-3, maxval=0.5)
    A = -jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0)
    B, C = (jax.random.normal(k, (b, n)) for k in ks[4:6])
    return state, x, dt, jnp.exp(dt * A), B, C


def _both(state, layer, x, dt, a, B, C):
    want = gh.mamba_step(state[:, layer], x, dt, a, B, C)
    got = pallas_mamba_step.mamba_step(
        state, layer, x, dt, a, B, C, interpret=True
    )
    return got, want


def _other_layers_untouched(new, state, layer):
    others = [i for i in range(state.shape[1]) if i != layer]
    np.testing.assert_array_equal(
        np.asarray(new[:, others]), np.asarray(state[:, others])
    )


@pytest.mark.parametrize("shape,layer", [
    # the published head shape: 64 heads of 64 x 128, two a tile
    ((2, 3, 64, 64, 128), 1),
    # a state of nine layers, the last named; 5 envs; four heads a tile
    ((5, 9, 8, 32, 128), 8),
    ((5, 9, 8, 32, 128), 0),
    # one layer, one env, sixteen heads a tile
    ((1, 1, 32, 8, 128), 0),
    # one head a tile
    ((2, 3, 2, 128, 128), 2),
])
def test_kernel_equals_the_plain_step_on_the_named_layer(shape, layer):
    state, x, dt, a, B, C = _inputs(*shape)
    assert pallas_mamba_step.fits(state)
    (new, y), (S_want, y_want) = _both(state, layer, x, dt, a, B, C)
    assert new.shape == state.shape and y.shape == x.shape
    np.testing.assert_allclose(new[:, layer], S_want, atol=1e-5)
    np.testing.assert_allclose(y, y_want, atol=1e-4, rtol=1e-5)
    # every other layer's bytes come back as they went in
    _other_layers_untouched(new, state, layer)
    # and it is the step, not a copy of its input
    assert float(jnp.max(jnp.abs(new[:, layer] - state[:, layer]))) > 0.1


@pytest.mark.parametrize("held", [1e6, jnp.inf, jnp.nan])
def test_a_reset_env_comes_back_as_a_fresh_start(held):
    """A decay of 0 for one env (``a * keep`` at a reset): its new
    state is ``dt x (x) B`` and its output ``dt x (B . C)``, whatever
    the state held; the other env, and the other layers of both, are
    untouched by it."""
    state, x, dt, a, B, C = _inputs(2, 3, 4, 64, 128, seed=1)
    layer = 1
    state = state.at[0].set(held)
    keep = jnp.array([0.0, 1.0])
    (new, y), (S_want, y_want) = _both(
        state, layer, x, dt, a * keep[:, None], B, C
    )
    dx = dt[0][:, None] * x[0]
    np.testing.assert_allclose(
        new[0, layer], dx[:, :, None] * B[0], atol=1e-6
    )
    np.testing.assert_allclose(
        y[0], dx * jnp.sum(B[0] * C[0]), atol=1e-4, rtol=1e-5
    )
    np.testing.assert_allclose(new[1, layer], S_want[1], atol=1e-5)
    np.testing.assert_allclose(y[1], y_want[1], atol=1e-4, rtol=1e-5)
    _other_layers_untouched(new, state, layer)


@pytest.mark.parametrize("case", ["a_0", "a_1", "dt_0"])
def test_the_decay_at_its_ends(case):
    """``a = 0``: the state is the step's own outer product. ``a = 1``:
    nothing is forgotten. ``dt = 0`` (and so ``a = 1``): the step
    changes nothing and reads the state out against ``C``."""
    state, x, dt, a, B, C = _inputs(2, 2, 4, 64, 128, seed=2)
    layer = 1
    if case == "a_0":
        a = jnp.zeros_like(a)
    elif case == "a_1":
        a = jnp.ones_like(a)
    else:
        dt, a = jnp.zeros_like(dt), jnp.ones_like(a)
    (new, y), (S_want, y_want) = _both(state, layer, x, dt, a, B, C)
    np.testing.assert_allclose(new[:, layer], S_want, atol=1e-5)
    np.testing.assert_allclose(y, y_want, atol=1e-4, rtol=1e-5)
    if case == "dt_0":
        np.testing.assert_array_equal(np.asarray(new), np.asarray(state))
        np.testing.assert_allclose(
            y, jnp.sum(state[:, layer] * C[:, None, None, :], -1),
            atol=1e-4, rtol=1e-5,
        )


def test_the_kernel_refuses_a_gradient():
    """The step form is never differentiated by a trainer: asking is a
    mistake, and says where to go instead."""
    state, x, dt, a, B, C = _inputs(1, 1, 16, 8, 128)

    def loss(state):
        _, y = pallas_mamba_step.mamba_step(
            state, 0, x, dt, a, B, C, interpret=True
        )
        return jnp.sum(y)

    with pytest.raises(NotImplementedError, match="chunk_state_space_scan"):
        jax.grad(loss)(state)


@pytest.mark.parametrize("preset,takes", [
    ("ppo-granite-recall", True), ("ppo-granite-tiny", False),
])
def test_only_widths_that_tile_the_vector_unit_take_the_kernel(
    preset, takes
):
    cfg = PRESETS[preset][1]["seq_model"]
    model = gh.GraniteHybridActorCritic(cfg, cache_len=8)
    state = jax.eval_shape(lambda: model.initialize_carry(2))["state"]
    assert state.dtype == jnp.float32
    assert pallas_mamba_step.fits(state) == takes
    if takes:
        assert state.shape[2:] == (64, 64, 128)
        # two lane tiles, rows that are no sublane tile, half a tile of rows
        for h, p, n in ((64, 64, 256), (32, 4, 128), (1, 64, 128)):
            assert not pallas_mamba_step.fits(jnp.zeros((1, 1, h, p, n)))
