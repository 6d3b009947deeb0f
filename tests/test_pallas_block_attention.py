"""The sequence form's attention kernels (ops/pallas_block_attention.py)
against the plain form they stand in for on the TPU
(models/decoder.py::attend under trajectory_mask's dense mask).

The suite runs on the CPU mesh, so every call passes ``interpret=True``:
the interpreter is never picked from the backend. The kernels compiled
for the chip at the timed shape are ``tests/test_tpu_hlo.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from actor_critic_algs_on_tensorflow_tpu.cli.train import PRESETS
from actor_critic_algs_on_tensorflow_tpu.models import decoder, sdar
from actor_critic_algs_on_tensorflow_tpu.ops import (
    pallas_block_attention as pba,
)

B, NH, NKV, HD = 2, 4, 2, 128
# a turn of ppo-sdar-turns: the env's block committed, four denoising
# passes over the policy's, its commit
TURN = [True, False, False, False, False, True]


def _attend(q, k, v, visible, dtype):
    """The plain form, its heads laid out as the kernels give them:
    ``[b, n, nh * hd]``."""
    return decoder.attend(q, k, v, visible, dtype).reshape(
        q.shape[:2] + (-1,)
    )


def _commits(pattern, T):
    """``commit [T, B]`` of a named pattern."""
    turn = jnp.resize(jnp.asarray(TURN), (T,))
    return {
        "turns": jnp.stack([turn] * B, 1),
        "no_commit": jnp.zeros((T, B), bool),
        "every_pass": jnp.ones((T, B), bool),
        "by_env": jnp.stack([turn, jnp.roll(~turn, 1)][:B], 1),
    }[pattern]


def _inputs(n, hdv=HD, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, n, NH, HD))
    k = jax.random.normal(ks[1], (B, n, NKV, HD))
    v = jax.random.normal(ks[2], (B, n, NKV, hdv))
    w = jax.random.normal(ks[3], (B, n, NH * hdv))
    return q, k, v, w


def _both(commit, block, dtype, tile_q, chunk):
    """The plain form and the kernels over one trajectory, as functions
    of ``(q, k, v)``."""
    _, step, key_commit = sdar.trajectory_steps(commit, block)
    _, visible = sdar.trajectory_mask(commit, block)

    def plain(q, k, v):
        return _attend(q, k, v, visible, dtype)

    def kernel(q, k, v):
        return pba.block_attention(
            q, k, v, step, key_commit, dtype, tile_q=tile_q, chunk=chunk,
            interpret=True,
        )

    return plain, kernel


def _grads(fn, w, *operands):
    return jax.grad(lambda *x: jnp.sum(fn(*x) * w), (0, 1, 2))(*operands)


# T passes of `block` positions, tiles of `tile_q` queries at most,
# chunks of `chunk` keys
SHAPES = {
    # four tiles of queries against three chunks of keys
    "tiles": (12, 4, 16, 16),
    # n = 40 is no whole chunk: the last chunk's keys are padded
    "padded": (10, 4, 8, 16),
    # the kernel's own chunk over a short range: 88 padded keys of 128
    "lane_tile": (10, 4, 256, 128),
    # a block of one position: with every pass a commit, causal
    "tokens": (24, 1, 8, 8),
}


@pytest.mark.parametrize("pattern", ["turns", "no_commit", "every_pass",
                                     "by_env"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernels_equal_the_plain_form_in_float32(pattern, shape):
    T, block, tile_q, chunk = SHAPES[shape]
    q, k, v, w = _inputs(T * block)
    plain, kernel = _both(
        _commits(pattern, T), block, jnp.float32, tile_q, chunk
    )
    want, got = plain(q, k, v), kernel(q, k, v)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, r, name in zip(_grads(kernel, w, q, k, v),
                          _grads(plain, w, q, k, v), "qkv"):
        assert g.shape == r.shape and g.dtype == r.dtype
        np.testing.assert_allclose(g, r, atol=3e-5, err_msg="d" + name)
    if block > 1:  # and it attends: no row is its own value
        assert float(jnp.max(jnp.abs(got[:, :, :HD] - v[:, :, 0]))) > 0.1


@pytest.mark.parametrize("pattern", ["turns", "by_env", "every_pass"])
def test_bfloat16_products_round_where_the_plain_form_rounds(pattern):
    """Operands and the NORMALISED probabilities in bfloat16, float32
    sums and softmax, the result rounded to bfloat16: against the plain
    form at the same precision, rounded the same, nearly every element
    is the same number and none is more than one rounding away; the
    gradients differ by one rounding of theirs."""
    T, block, tile_q, chunk = SHAPES["tiles"]
    q, k, v, w = _inputs(T * block, seed=1)
    commit = _commits(pattern, T)
    plain, kernel = _both(commit, block, jnp.bfloat16, tile_q, chunk)
    exact, _ = _both(commit, block, jnp.float32, tile_q, chunk)
    want, got = plain(q, k, v), kernel(q, k, v)
    assert got.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(want - exact(q, k, v)))) > 1e-3
    rounded = want.astype(jnp.bfloat16)
    assert float(jnp.mean(got != rounded)) < 0.01
    ulp = 2.0 ** -7 * jnp.abs(want)
    assert bool(jnp.all(jnp.abs(got.astype(jnp.float32) - want) <= ulp))
    for g, r in zip(_grads(kernel, w, q, k, v), _grads(plain, w, q, k, v)):
        assert g.dtype == r.dtype
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g, r, atol=scale * 2.0 ** -6)


def test_the_causal_case_is_a_block_of_one_and_every_pass_a_commit():
    n = 24
    q, k, v, _ = _inputs(n, seed=2)
    got = pba.block_attention(
        q, k, v, jnp.arange(n), jnp.ones((B, n), bool), jnp.float32,
        tile_q=8, chunk=8, interpret=True,
    )
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((n, n), bool)), (B, n, n))
    np.testing.assert_allclose(
        got, _attend(q, k, v, causal, jnp.float32), atol=1e-5
    )


def test_values_may_be_wider_than_keys():
    """Latent attention's expanded form has values of another width
    than its keys; ``decoder.attend`` has not, so the plain form is
    written out."""
    T, block, tile_q, chunk = SHAPES["padded"]
    q, k, v, w = _inputs(T * block, hdv=2 * HD, seed=3)
    commit = _commits("turns", T)
    _, kernel = _both(commit, block, jnp.float32, tile_q, chunk)
    _, visible = sdar.trajectory_mask(commit, block)

    def plain(q, k, v):
        qg = q.reshape(B, -1, NKV, NH // NKV, HD)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * HD ** -0.5
        probs = jax.nn.softmax(
            jnp.where(visible[:, None, None], scores, -jnp.inf), axis=-1
        )
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(
            B, -1, NH * 2 * HD
        )

    got = kernel(q, k, v)
    assert got.shape == (B, T * block, NH * 2 * HD)
    np.testing.assert_allclose(got, plain(q, k, v), atol=1e-5)
    for g, r in zip(_grads(kernel, w, q, k, v), _grads(plain, w, q, k, v)):
        np.testing.assert_allclose(g, r, atol=3e-5)


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_padded_keys_reach_no_result(chunk):
    """The keys' range is padded to whole chunks inside the op: whatever
    the chunk, 40 keys give what 40 keys give, and the gradients have
    their 40 rows."""
    T, block, tile_q, _ = SHAPES["padded"]
    q, k, v, w = _inputs(T * block, seed=4)
    commit = _commits("by_env", T)
    _, whole = _both(commit, block, jnp.float32, tile_q, 8)  # no padding
    _, padded = _both(commit, block, jnp.float32, tile_q, chunk)
    np.testing.assert_allclose(padded(q, k, v), whole(q, k, v), atol=1e-5)
    for g, r in zip(_grads(padded, w, q, k, v), _grads(whole, w, q, k, v)):
        assert g.shape == r.shape and np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(g, r, atol=1e-5)


@pytest.mark.parametrize("T,block,tile_q,chunk,share", [
    # tiles and chunks of 16 over 48 positions: 1 + 2 + 3 of 9
    (12, 4, 16, 16, 6 / 9),
    # the cell's range at the kernel's sizes: tiles of 192 against
    # chunks of 128, 2 + 3 + 5 of 15
    (144, 4, 256, 128, 10 / 15),
    # tiles of 64: 1 1 2 2 3 3 4 4 5 of 45
    (144, 4, 64, 128, 25 / 45),
    # one tile sees every chunk
    (10, 4, 256, 8, 1.0),
    # a pass that straddles two chunks is in both: blocks of 6, chunks
    # of 8, tiles of 24 over 48 positions see keys below 24 and 48
    (8, 6, 24, 8, (3 + 6) / 12),
])
def test_score_tiles_computed_share_counts_the_visited_pairs(
        T, block, tile_q, chunk, share):
    step = jnp.repeat(jnp.arange(T), block)
    got = pba.score_tiles_computed_share(step, tile_q=tile_q, chunk=chunk)
    assert float(got) == pytest.approx(share)


def test_only_the_published_heads_take_the_kernels():
    def heads(n, nh, nkv, hd, hdv=None):
        return [jax.ShapeDtypeStruct((2, n, h, d), jnp.float32)
                for h, d in ((nh, hd), (nkv, hd), (nkv, hdv or hd))]

    assert pba.fits(*heads(576, 32, 4, 128))
    assert pba.fits(*heads(512, 16, 16, 128, 256))
    # a narrow head, narrow values, a range that is no whole sublane
    # tile, heads that do not share the key/value heads evenly
    assert not pba.fits(*heads(576, 32, 4, 64))
    assert not pba.fits(*heads(576, 32, 4, 128, 64))
    assert not pba.fits(*heads(572, 32, 4, 128))
    assert not pba.fits(*heads(576, 6, 4, 128))
    # the test preset's heads go to the plain form, which says that it
    # computed every score
    cfg = PRESETS["ppo-sdar-tiny"][1]["seq_model"]
    tiny = heads(96, cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    assert not pba.fits(*tiny)
    step = jnp.repeat(jnp.arange(24), 4)
    assert float(sdar._score_tiles_computed_share(*tiny, step)) == 1.0
    with pytest.raises(ValueError, match="whole sublane tiles"):
        q, k, v, _ = _inputs(12)
        pba.block_attention(q, k, v, jnp.arange(12), jnp.ones((B, 12), bool),
                            jnp.float32, interpret=True)
