"""Lightweight policy distributions as JAX pytrees.

Capability parity: the reference's policies are a softmax head for
discrete control (CartPole / Atari — BASELINE.json:7-8), a deterministic
+ OU-noise actor for DDPG (BASELINE.json:9), and a squashed-Gaussian
actor with learned entropy temperature for SAC (BASELINE.json:10).
These classes provide sample / log_prob / entropy as pure functions on
arrays so they can live inside jitted update steps; no external
distribution library is used.

``BlockReveal`` is the policy of a block-diffusion language model (one
denoising pass over a block of tokens; ``models/sdar.py``).

Implemented as ``NamedTuple`` pytrees: they flatten transparently
through ``jax.jit`` / ``lax.scan`` / ``shard_map`` boundaries.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

_LOG_SQRT_2PI = 0.5 * math.log(2.0 * math.pi)


class Categorical(NamedTuple):
    """Categorical distribution over logits ``[..., A]``."""

    logits: jax.Array

    def sample(self, key: jax.Array) -> jax.Array:
        return jax.random.categorical(key, self.logits, axis=-1)

    def mode(self) -> jax.Array:
        return jnp.argmax(self.logits, axis=-1)

    def log_prob(self, actions: jax.Array) -> jax.Array:
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        return jnp.take_along_axis(
            log_p, actions[..., None].astype(jnp.int32), axis=-1
        )[..., 0]

    def entropy(self) -> jax.Array:
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        p = jnp.exp(log_p)
        return -jnp.sum(p * log_p, axis=-1)

    def log_prob_and_entropy(self, actions: jax.Array):
        """``(log_prob(actions), entropy())`` from one pass of row
        statistics, float32, with a hand-written backward (see
        ``_log_prob_and_entropy``)."""
        return _log_prob_and_entropy(
            self.logits.astype(jnp.float32), actions.astype(jnp.int32)
        )

    def kl(self, other: "Categorical") -> jax.Array:
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        log_q = jax.nn.log_softmax(other.logits, axis=-1)
        return jnp.sum(jnp.exp(log_p) * (log_p - log_q), axis=-1)


def _row_statistics(z, actions):
    """``(log p(a), H, (m, log S))`` of float32 logits ``z [..., V]``:
    the row's max ``m``, then one pass that sums ``e = exp(z - m)`` to
    ``S`` and ``e (z - m)``. Taken about the max, ``log p = (z - m) -
    log S`` and ``H = lse - sum p z = log S - sum e (z - m) / S``."""
    m = jnp.max(z, axis=-1, keepdims=True)
    d = z - m
    e = jnp.exp(d)
    total = jnp.sum(e, axis=-1, keepdims=True)
    log_total = jnp.log(total)
    entropy = log_total - jnp.sum(e * d, axis=-1, keepdims=True) / total
    # read off z, not d: a gather from d would write d out
    taken = jnp.take_along_axis(z, actions[..., None], axis=-1) - m
    return (taken - log_total)[..., 0], entropy[..., 0], (m, log_total)


@jax.custom_vjp
def _log_prob_and_entropy(z, actions):
    log_prob, entropy, _ = _row_statistics(z, actions)
    return log_prob, entropy


def _log_prob_and_entropy_fwd(z, actions):
    log_prob, entropy, shift = _row_statistics(z, actions)
    return (log_prob, entropy), (z, shift, entropy, actions)


def _log_prob_and_entropy_bwd(res, g):
    """``dz = g_lp (onehot(a) - p) - g_H p (log p + H)``, ``log p = z -
    lse``: one read of ``z``, one write of ``dz``."""
    z, (m, log_total), entropy, actions = res
    g_lp, g_h = (x[..., None] for x in g)
    log_p = (z - m) - log_total
    onehot = actions[..., None] == jnp.arange(z.shape[-1], dtype=jnp.int32)
    dz = jnp.where(onehot, g_lp, 0.0) - jnp.exp(log_p) * (
        g_lp + g_h * (log_p + entropy[..., None])
    )
    return dz, None


_log_prob_and_entropy.defvjp(
    _log_prob_and_entropy_fwd, _log_prob_and_entropy_bwd
)


class BlockReveal(NamedTuple):
    """One denoising pass of generation by diffusion over blocks, as a
    distribution over the block after the pass.

    ``logits [..., L, V]`` over the ``L`` positions of a block, the mask
    token's column at ``-inf``; ``block [..., L]`` the observed ids,
    ``mask_id`` where a position is still masked; ``reveal`` how many
    masked positions a pass reveals (the static low-confidence
    schedule, ``block_length / denoising_steps``). The action is the
    block after the pass: the drawn id where a position was revealed,
    the observed id elsewhere (``mask_id`` where still masked). The
    log-probability scores the ids revealed and not WHICH positions
    were: the choice is the sampler's (TraceRL's objective,
    arXiv:2509.06949). A pass over a block with no mask — a commit
    pass — reveals nothing: log-probability and entropy 0.
    """

    logits: jax.Array
    block: jax.Array
    reveal: int
    mask_id: int

    def sample(self, key: jax.Array) -> jax.Array:
        """Draw an id at every masked position (temperature 1) and keep
        the ``reveal`` drawn with the highest confidence ``p_i(x_i)``
        (ties to the lowest index; all where fewer are masked)."""
        drawn = jax.random.categorical(key, self.logits, axis=-1)
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        masked = self.block == self.mask_id
        confidence = jnp.where(
            masked,
            jnp.take_along_axis(log_p, drawn[..., None], axis=-1)[..., 0],
            -jnp.inf,
        )
        # a position's rank in its block: how many others go before it
        mine, other = confidence[..., :, None], confidence[..., None, :]
        index = jnp.arange(self.block.shape[-1])
        before = (other > mine) | (
            (other == mine) & (index[None, :] < index[:, None])
        )
        revealed = masked & (jnp.sum(before, axis=-1) < self.reveal)
        return jnp.where(revealed, drawn, self.block).astype(self.block.dtype)

    def _scored(self, actions: jax.Array) -> jax.Array:
        return (self.block == self.mask_id) & (actions != self.mask_id)

    def log_prob(self, actions: jax.Array) -> jax.Array:
        scored = self._scored(actions)
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        # an unscored position reads column 0, never the mask's -inf
        ids = jnp.where(scored, actions, 0).astype(jnp.int32)
        taken = jnp.take_along_axis(log_p, ids[..., None], axis=-1)[..., 0]
        return jnp.sum(jnp.where(scored, taken, 0.0), axis=-1)

    def log_prob_and_entropy(self, actions: jax.Array):
        return self.log_prob(actions), self.entropy()

    def entropy(self) -> jax.Array:
        """Mean categorical entropy over the masked positions."""
        masked = self.block == self.mask_id
        log_p = jax.nn.log_softmax(self.logits, axis=-1)
        # the mask's column: probability 0 times a log-probability
        # that is -inf; zeroed before the product, for the gradient too
        finite = jnp.where(jnp.isfinite(log_p), log_p, 0.0)
        per_position = -jnp.sum(jnp.exp(log_p) * finite, axis=-1)
        return jnp.sum(jnp.where(masked, per_position, 0.0), axis=-1) / (
            jnp.maximum(jnp.sum(masked, axis=-1), 1)
        )


class DiagGaussian(NamedTuple):
    """Diagonal Gaussian with event shape ``[..., D]``."""

    mean: jax.Array
    log_std: jax.Array

    def sample(self, key: jax.Array) -> jax.Array:
        eps = jax.random.normal(key, self.mean.shape, self.mean.dtype)
        return self.mean + jnp.exp(self.log_std) * eps

    def mode(self) -> jax.Array:
        return self.mean

    def log_prob(self, actions: jax.Array) -> jax.Array:
        z = (actions - self.mean) * jnp.exp(-self.log_std)
        per_dim = -0.5 * z * z - self.log_std - _LOG_SQRT_2PI
        return jnp.sum(per_dim, axis=-1)

    def entropy(self) -> jax.Array:
        return jnp.sum(self.log_std + 0.5 + _LOG_SQRT_2PI, axis=-1)


class TanhGaussian(NamedTuple):
    """Tanh-squashed diagonal Gaussian (SAC actor, BASELINE.json:10).

    ``sample_and_log_prob`` applies the change-of-variables correction

        log pi(a) = log N(u) - sum_i log(1 - tanh(u_i)^2)

    using the numerically stable identity
    ``log(1 - tanh(u)^2) = 2 * (log 2 - u - softplus(-2u))``.
    """

    mean: jax.Array
    log_std: jax.Array

    def _base(self) -> DiagGaussian:
        return DiagGaussian(self.mean, self.log_std)

    def sample_and_log_prob(self, key: jax.Array):
        u = self._base().sample(key)
        a = jnp.tanh(u)
        log_p = self._base().log_prob(u) - jnp.sum(
            _tanh_log_det_jacobian(u), axis=-1
        )
        return a, log_p

    def sample(self, key: jax.Array) -> jax.Array:
        return jnp.tanh(self._base().sample(key))

    def mode(self) -> jax.Array:
        return jnp.tanh(self.mean)

    def log_prob_from_pre_tanh(self, u: jax.Array) -> jax.Array:
        return self._base().log_prob(u) - jnp.sum(
            _tanh_log_det_jacobian(u), axis=-1
        )


def _tanh_log_det_jacobian(u: jax.Array) -> jax.Array:
    return 2.0 * (math.log(2.0) - u - jax.nn.softplus(-2.0 * u))
