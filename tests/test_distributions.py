"""Distribution sample/log_prob/entropy checks, incl. the tanh-squash
correction numeric check (SURVEY.md §4.1)."""

import jax
import jax.numpy as jnp
import numpy as np

import pytest

from actor_critic_algs_on_tensorflow_tpu.ops import (
    BlockReveal,
    Categorical,
    DiagGaussian,
    TanhGaussian,
)


def test_categorical_log_prob_and_entropy():
    logits = jnp.asarray([[1.0, 2.0, 0.5], [0.0, 0.0, 0.0]])
    d = Categorical(logits)
    p = np.exp(np.asarray(logits[0])) / np.exp(np.asarray(logits[0])).sum()
    np.testing.assert_allclose(
        float(d.log_prob(jnp.asarray([1, 2]))[0]), np.log(p[1]), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(d.entropy()[1]), np.log(3.0), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(d.entropy()[0]), -(p * np.log(p)).sum(), rtol=1e-5
    )


def test_categorical_sample_distribution():
    logits = jnp.asarray([0.0, 1.0, 2.0])
    d = Categorical(logits)
    keys = jax.random.split(jax.random.PRNGKey(0), 20000)
    samples = jax.vmap(d.sample)(keys)
    freq = np.bincount(np.asarray(samples), minlength=3) / 20000
    p = np.exp([0.0, 1.0, 2.0]) / np.exp([0.0, 1.0, 2.0]).sum()
    np.testing.assert_allclose(freq, p, atol=0.02)


def test_diag_gaussian_log_prob_vs_scipy_formula():
    mean = jnp.asarray([0.3, -0.7])
    log_std = jnp.asarray([0.1, -0.5])
    x = jnp.asarray([0.0, 0.2])
    d = DiagGaussian(mean, log_std)
    std = np.exp(np.asarray(log_std))
    expected = (
        -0.5 * ((np.asarray(x) - np.asarray(mean)) / std) ** 2
        - np.log(std)
        - 0.5 * np.log(2 * np.pi)
    ).sum()
    np.testing.assert_allclose(float(d.log_prob(x)), expected, rtol=1e-5)
    expected_ent = (np.log(std) + 0.5 * (1 + np.log(2 * np.pi))).sum()
    np.testing.assert_allclose(float(d.entropy()), expected_ent, rtol=1e-5)


def test_tanh_gaussian_log_prob_change_of_variables():
    """log pi(a) must equal log N(u) - sum log|d tanh/du| evaluated
    naively (in a regime where the naive formula is stable)."""
    mean = jnp.asarray([0.1, -0.2])
    log_std = jnp.asarray([-1.0, -0.8])
    d = TanhGaussian(mean, log_std)
    a, logp = d.sample_and_log_prob(jax.random.PRNGKey(42))
    u = np.arctanh(np.clip(np.asarray(a), -0.999999, 0.999999))
    std = np.exp(np.asarray(log_std))
    base = (
        -0.5 * ((u - np.asarray(mean)) / std) ** 2
        - np.log(std)
        - 0.5 * np.log(2 * np.pi)
    ).sum()
    naive = base - np.log(1.0 - np.tanh(u) ** 2).sum()
    np.testing.assert_allclose(float(logp), naive, rtol=1e-4)
    assert np.all(np.abs(np.asarray(a)) <= 1.0)


def test_tanh_gaussian_integrates_to_one_1d():
    """Numerically integrate exp(log_prob) over (-1, 1) in 1-D."""
    d = TanhGaussian(jnp.asarray([0.4]), jnp.asarray([-0.3]))
    a = np.linspace(-0.9999, 0.9999, 40001)
    u = np.arctanh(a)
    logp = jax.vmap(d.log_prob_from_pre_tanh)(jnp.asarray(u)[:, None])
    total = np.trapezoid(np.exp(np.asarray(logp)), a)
    np.testing.assert_allclose(total, 1.0, atol=1e-3)


# BlockReveal: one denoising pass over a block (models/sdar.py's policy)

MASK = 7  # the last of 8 ids


def _block_logits(seed=0, shape=(5, 4)):
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(seed), shape + (8,))
    return logits.at[..., MASK].set(-jnp.inf)


@pytest.mark.parametrize("reveal", [1, 2, 4])
def test_block_reveal_reveals_exactly_its_count(reveal):
    """``reveal`` of the masked positions change a pass (all of them
    where fewer are masked), clean positions never do, and a mask is
    never drawn."""
    block = jnp.asarray([[MASK, MASK, MASK, MASK], [3, MASK, MASK, 1],
                         [MASK, 2, 2, 2], [0, 1, 2, 3], [MASK, MASK, 5, MASK]])
    d = BlockReveal(_block_logits(), block, reveal, MASK)
    for key in jax.random.split(jax.random.PRNGKey(1), 20):
        after = np.asarray(d.sample(key))
        masked = np.asarray(block) == MASK
        revealed = masked & (after != MASK)
        np.testing.assert_array_equal(
            revealed.sum(-1), np.minimum(masked.sum(-1), reveal)
        )
        np.testing.assert_array_equal(after[~masked], np.asarray(block)[~masked])
        assert (after[revealed] < MASK).all()
        assert after.dtype == block.dtype


def test_block_reveal_keeps_the_most_confident_and_breaks_ties_low():
    """Of the ids drawn, the one its position gives the highest
    probability is revealed; equal confidences go to the lowest index."""
    sharp = jnp.full((4, 8), -20.0).at[:, MASK].set(-jnp.inf)
    # position 2 is all but certain of id 5; the others are uniform
    logits = jnp.zeros((4, 8)).at[:, MASK].set(-jnp.inf).at[2].set(
        sharp[2].at[5].set(20.0)
    )
    block = jnp.full((4,), MASK)
    for key in jax.random.split(jax.random.PRNGKey(2), 10):
        after = np.asarray(BlockReveal(logits, block, 1, MASK).sample(key))
        assert after.tolist() == [MASK, MASK, 5, MASK]
    # every position certain of its id: confidence 1 everywhere
    certain = sharp.at[jnp.arange(4), jnp.arange(4)].set(20.0)
    after = BlockReveal(certain, block, 2, MASK).sample(jax.random.PRNGKey(3))
    assert np.asarray(after).tolist() == [0, 1, MASK, MASK]
    after = BlockReveal(certain, block.at[0].set(6), 2, MASK).sample(
        jax.random.PRNGKey(3)
    )
    assert np.asarray(after).tolist() == [6, 1, 2, MASK]


def test_block_reveal_scores_only_what_it_revealed():
    logits = _block_logits(4, (4,))
    log_p = np.asarray(jax.nn.log_softmax(logits, -1))
    block = jnp.asarray([MASK, 3, MASK, MASK])
    d = BlockReveal(logits, block, 1, MASK)
    # position 2 revealed as id 1; position 1 was clean; 0 and 3 stay
    np.testing.assert_allclose(
        float(d.log_prob(jnp.asarray([MASK, 3, 1, MASK]))), log_p[2, 1],
        rtol=1e-6,
    )
    # two revealed: the sum; which positions were chosen is not scored
    np.testing.assert_allclose(
        float(d.log_prob(jnp.asarray([4, 3, 1, MASK]))),
        log_p[0, 4] + log_p[2, 1], rtol=1e-6,
    )
    # nothing revealed, and a clean block (a commit pass): exactly 0
    assert float(d.log_prob(block)) == 0.0
    clean = BlockReveal(logits, jnp.asarray([0, 1, 2, 3]), 1, MASK)
    assert float(clean.log_prob(jnp.asarray([0, 1, 2, 3]))) == 0.0
    assert float(clean.entropy()) == 0.0
    # its own sample's log-probability is the drawn ids' at the
    # revealed positions
    after = d.sample(jax.random.PRNGKey(5))
    i = int(np.flatnonzero((np.asarray(block) == MASK)
                           & (np.asarray(after) != MASK))[0])
    np.testing.assert_allclose(
        float(d.log_prob(after)), log_p[i, int(after[i])], rtol=1e-6
    )


def test_block_reveal_entropy_is_the_masked_positions_mean():
    logits = _block_logits(6, (4,))
    log_p = np.asarray(jax.nn.log_softmax(logits[:, :MASK], -1))
    per_position = -(np.exp(log_p) * log_p).sum(-1)
    d = BlockReveal(logits, jnp.asarray([MASK, 3, MASK, 0]), 1, MASK)
    np.testing.assert_allclose(
        float(d.entropy()), per_position[[0, 2]].mean(), rtol=1e-5
    )
    # finite gradients though the mask's column is -inf
    grad = jax.grad(lambda lg: BlockReveal(
        lg, jnp.asarray([MASK, 3, MASK, 0]), 1, MASK
    ).entropy())(logits)
    assert np.isfinite(np.asarray(grad)).all()
    assert float(jnp.max(jnp.abs(grad[1]))) == 0.0  # a clean position


@pytest.mark.parametrize("vocab", [6, 200, 8192])
def test_categorical_log_prob_and_entropy_from_one_pass(vocab):
    """``log_prob_and_entropy`` over ``[T, b]`` rows is ``log_prob`` and
    ``entropy`` at float32 rounding, and so is the gradient of a loss
    that weights both (its own backward against JAX's derivative of the
    two); both agree with float64."""
    k_z, k_a, k_w = jax.random.split(jax.random.PRNGKey(vocab), 3)
    logits = 3.0 * jax.random.normal(k_z, (5, 4, vocab)) + 40.0
    actions = jax.random.randint(k_a, (5, 4), 0, vocab)
    w_lp, w_h = jax.random.normal(k_w, (2, 5, 4))

    def loss(lg, one_pass):
        d = Categorical(lg)
        if one_pass:
            lp, h = d.log_prob_and_entropy(actions)
        else:
            lp, h = d.log_prob(actions), d.entropy()
        return jnp.sum(w_lp * lp + w_h * h), (lp, h)

    (_, (lp, h)), grad = jax.jit(jax.value_and_grad(
        lambda lg: loss(lg, True), has_aux=True
    ))(logits)
    (_, (lp0, h0)), grad0 = jax.jit(jax.value_and_grad(
        lambda lg: loss(lg, False), has_aux=True
    ))(logits)
    np.testing.assert_allclose(lp, lp0, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(h, h0, rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(grad, grad0, rtol=1e-5, atol=1e-6)

    z = np.asarray(logits, np.float64)
    log_p = z - z.max(-1, keepdims=True)
    log_p -= np.log(np.exp(log_p).sum(-1, keepdims=True))
    p = np.exp(log_p)
    ent = -(p * log_p).sum(-1)
    taken = np.take_along_axis(log_p, np.asarray(actions)[..., None], -1)
    np.testing.assert_allclose(lp, taken[..., 0], rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(h, ent, rtol=1e-6, atol=2e-6)
    onehot = np.arange(vocab) == np.asarray(actions)[..., None]
    w_lp, w_h = (np.asarray(w, np.float64)[..., None] for w in (w_lp, w_h))
    expected = w_lp * (onehot - p) - w_h * p * (log_p + ent[..., None])
    np.testing.assert_allclose(grad, expected, rtol=1e-5, atol=1e-6)
    # with no gradient taken, the forward alone
    lp1, h1 = jax.jit(Categorical(logits).log_prob_and_entropy)(actions)
    np.testing.assert_allclose(lp1, taken[..., 0], rtol=1e-6, atol=2e-6)
    np.testing.assert_allclose(h1, ent, rtol=1e-6, atol=2e-6)


def test_block_reveal_log_prob_and_entropy_is_its_two_methods():
    logits = _block_logits(6, (3, 4))
    block = jnp.asarray([[MASK, 3, MASK, 0], [0, 1, 2, 3],
                         [MASK, MASK, MASK, MASK]])
    actions = jnp.asarray([[4, 3, MASK, 0], [0, 1, 2, 3], [1, MASK, 2, 5]])
    d = BlockReveal(logits, block, 2, MASK)
    lp, h = d.log_prob_and_entropy(actions)
    np.testing.assert_array_equal(lp, d.log_prob(actions))
    np.testing.assert_array_equal(h, d.entropy())
