"""The inverse of many unit-lower-triangular matrices
(ops/pallas_unit_lower_inverse.py) against the triangular solve it
stands in for on the TPU (models/qwen3_next.py::_plain_inverse), and
against a float64 inverse.

The suite runs on the CPU, so every call passes ``interpret=True``: the
interpreter is never picked from the backend. The kernel compiled for
the chip at the timed shape is ``tests/test_tpu_hlo.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.models import qwen3_next as qn
from actor_critic_algs_on_tensorflow_tpu.ops import pallas_unit_lower_inverse
from tests.helpers import chunk_matrices


@pytest.mark.parametrize("lead,c", [
    # the published chunk, one grid step of 128 matrices
    ((128,), 64),
    # narrow chunks, two grid steps
    ((2, 128), 8),
    # several leading axes
    ((2, 2, 64), 16),
])
@pytest.mark.parametrize("repeated", [False, True])
def test_kernel_equals_the_plain_inverse(lead, c, repeated):
    a = chunk_matrices(lead, c, repeated)
    assert pallas_unit_lower_inverse.fits(a)
    got = pallas_unit_lower_inverse.inverse(a, interpret=True)
    want = qn._plain_inverse(a)
    assert got.shape == a.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, atol=1e-6 * scale)
    # and it is the inverse, to float32's rounding
    exact = np.linalg.inv(np.asarray(a, np.float64) + np.eye(c))
    assert float(np.max(np.abs(np.asarray(got) - exact))) < 1e-5 * scale
    if repeated:
        assert float(jnp.max(a)) > 0.9


def test_what_lies_on_and_above_the_diagonal_is_not_read():
    a = chunk_matrices((128,), 16, repeated=False, seed=3)
    noise = jax.random.normal(jax.random.PRNGKey(4), a.shape)
    upper = noise * jnp.triu(jnp.ones((16, 16)))
    np.testing.assert_array_equal(
        pallas_unit_lower_inverse.inverse(a + upper, interpret=True),
        pallas_unit_lower_inverse.inverse(a, interpret=True),
    )


def test_only_shapes_that_tile_the_vector_unit_take_the_kernel():
    fits = pallas_unit_lower_inverse.fits
    assert fits(jnp.zeros((4, 32, 32, 64, 64)))
    assert not fits(jnp.zeros((2, 3, 8, 8)))        # 6 matrices
    assert not fits(jnp.zeros((128, 12, 12)))       # c not whole sublanes
    assert not fits(jnp.zeros((128, 256, 256)))     # c beyond a lane tile
    assert not fits(jnp.zeros((128, 8, 16)))        # not square
