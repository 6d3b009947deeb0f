"""Flax policy/value networks.

Capability parity (BASELINE.json:7-10): a 2-layer MLP policy for
CartPole, the Nature-CNN encoder for Atari-class 84x84x4 observations,
continuous-control actor/critic pairs for DDPG, and a twin-Q critic +
squashed-Gaussian actor for SAC. All modules are plain ``flax.linen``
so they jit/pjit/vmap transparently; compute dtype is configurable so
the MXU path can run bfloat16 with float32 params.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import flax.linen as nn
import math

import jax
import jax.numpy as jnp

from actor_critic_algs_on_tensorflow_tpu.ops.ring_attention import (
    ring_attention,
)

Dtype = Any


def _orthogonal(scale: float = math.sqrt(2.0)):
    return nn.initializers.orthogonal(scale)


def _symmetric_uniform(bound: float):
    """U[-bound, bound] init (DDPG paper's final-layer init)."""

    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


class MLPTorso(nn.Module):
    """Feed-forward torso; default 2x64 tanh (CartPole-class policies)."""

    hidden_sizes: Sequence[int] = (64, 64)
    activation: Callable = nn.tanh
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for h in self.hidden_sizes:
            x = nn.Dense(h, kernel_init=_orthogonal(), dtype=self.dtype)(x)
            x = self.activation(x)
        return x


def scale_pixels(x, dtype):
    """Frames in ``dtype``: uint8 scaled to [0, 1] on the device (the
    host->HBM transfer stays 1 byte/pixel), anything else cast."""
    if x.dtype == jnp.uint8:
        return x.astype(dtype) / 255.0
    return x.astype(dtype)


class NatureCNN(nn.Module):
    """Nature-DQN convolutional encoder for 84x84 stacked frames.

    Conv(32,8x8,s4) -> Conv(64,4x4,s2) -> Conv(64,3x3,s1) -> Dense(512),
    ReLU throughout (Mnih et al. 2015). Input ``[..., 84, 84, C]`` in
    [0, 1] or uint8 (uint8 is scaled on-device so the host->HBM transfer
    stays 1 byte/pixel).
    """

    hidden_size: int = 512
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = scale_pixels(x, self.dtype)
        batch_shape = x.shape[:-3]
        x = x.reshape((-1,) + x.shape[-3:])
        for features, kernel, stride in ((32, 8, 4), (64, 4, 2), (64, 3, 1)):
            x = nn.Conv(
                features,
                (kernel, kernel),
                strides=(stride, stride),
                padding="VALID",
                kernel_init=_orthogonal(),
                dtype=self.dtype,
            )(x)
            x = nn.relu(x)
        x = x.reshape(x.shape[0], -1)
        x = nn.Dense(self.hidden_size, kernel_init=_orthogonal(), dtype=self.dtype)(x)
        x = nn.relu(x)
        return x.reshape(batch_shape + (self.hidden_size,))


def _sinusoidal_positions(positions, d_model, dtype):
    """Sinusoidal position embedding for (possibly shard-offset) indices."""
    half = d_model // 2
    freqs = jnp.exp(
        -jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / half
    )
    angles = positions.astype(jnp.float32)[..., None] * freqs
    emb = jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)
    return emb.astype(dtype)


class TransformerTorso(nn.Module):
    """Pre-LN transformer encoder over a token sequence.

    Attention runs through ``ops.ring_attention``, so the SAME module
    serves single-device policies (``axis_name=None``, one blockwise
    pass) and long-history policies whose token axis is sharded over a
    mesh axis inside ``shard_map`` (``axis_name='time'`` + positions
    offset per shard) — the framework's attention-model long-context
    path, complementing the sequence-parallel temporal scans.

    Input ``[..., L, F]`` tokens; output ``[..., d_model]`` (mean-pooled)
    or ``[..., L, d_model]`` with ``pool=False``.
    """

    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    mlp_ratio: int = 4
    causal: bool = True
    axis_name: str | None = None
    pool: bool = True
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        batch_shape = tokens.shape[:-2]
        seq_len, feat = tokens.shape[-2:]
        x = tokens.reshape((-1, seq_len, feat)).astype(self.dtype)
        x = nn.Dense(self.d_model, kernel_init=_orthogonal(), dtype=self.dtype)(x)
        if self.axis_name is None:
            positions = jnp.arange(seq_len)
        else:
            positions = (
                jax.lax.axis_index(self.axis_name) * seq_len
                + jnp.arange(seq_len)
            )
        x = x + _sinusoidal_positions(positions, self.d_model, self.dtype)

        head_dim = self.d_model // self.num_heads
        for _ in range(self.num_layers):
            h = nn.LayerNorm(dtype=self.dtype)(x)
            qkv = nn.Dense(
                3 * self.d_model, kernel_init=_orthogonal(), dtype=self.dtype
            )(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            shape = (x.shape[0], seq_len, self.num_heads, head_dim)
            attn = ring_attention(
                q.reshape(shape), k.reshape(shape), v.reshape(shape),
                axis_name=self.axis_name, causal=self.causal,
            )
            attn = attn.reshape(x.shape[0], seq_len, self.d_model)
            x = x + nn.Dense(
                self.d_model, kernel_init=_orthogonal(), dtype=self.dtype
            )(attn)
            h = nn.LayerNorm(dtype=self.dtype)(x)
            h = nn.Dense(
                self.mlp_ratio * self.d_model,
                kernel_init=_orthogonal(),
                dtype=self.dtype,
            )(h)
            h = nn.gelu(h)
            x = x + nn.Dense(
                self.d_model, kernel_init=_orthogonal(), dtype=self.dtype
            )(h)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if self.pool:
            x = x.mean(axis=-2)
            if self.axis_name is not None:
                # Local means are per-shard; equal shard lengths make
                # their pmean the exact global-token mean.
                x = jax.lax.pmean(x, self.axis_name)
            return x.reshape(batch_shape + (self.d_model,))
        return x.reshape(batch_shape + (seq_len, self.d_model))


class FrameTransformerEncoder(nn.Module):
    """Atari-class encoder: per-frame Nature-CNN features as tokens,
    attended over the frame-history axis by ``TransformerTorso``.

    The attention-based alternative to channel-stacked ``NatureCNN``:
    input ``[..., 84, 84, C]`` (C stacked frames) becomes C one-channel
    tokens, so the history length is decoupled from the conv input
    channels and can grow to long contexts (sharded via ``axis_name``).
    """

    hidden_size: int = 256
    d_model: int = 128
    num_heads: int = 4
    num_layers: int = 2
    axis_name: str | None = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        frames = jnp.moveaxis(obs[..., None], -2, -4)  # [..., C, 84, 84, 1]
        tokens = NatureCNN(hidden_size=self.hidden_size, dtype=self.dtype)(
            frames
        )  # [..., C, hidden]
        return TransformerTorso(
            d_model=self.d_model,
            num_heads=self.num_heads,
            num_layers=self.num_layers,
            causal=True,
            axis_name=self.axis_name,
            dtype=self.dtype,
        )(tokens)


def _discrete_torso(name, hidden_sizes, dtype):
    """The encoder that ``DiscreteActorCritic`` and
    ``RecurrentActorCritic`` put under their heads for ``torso=name``.
    Any other name is refused: falling back to the MLP would train a
    different model than the one asked for."""
    if name == "nature_cnn":
        return NatureCNN(dtype=dtype)
    if name == "frame_transformer":
        return FrameTransformerEncoder(dtype=dtype)
    if name == "mlp":
        return MLPTorso(hidden_sizes, dtype=dtype)
    raise ValueError(
        f"unknown torso {name!r}: this module builds 'mlp', 'nature_cnn' "
        "or 'frame_transformer'"
    )


class _MaskedLSTMCell(nn.Module):
    """LSTM cell step with per-example episode-boundary masking.

    ``xs = (z, reset)``: the carry is zeroed where ``reset == 1`` BEFORE
    the cell runs, so a step that begins a new episode cannot see state
    from the previous one. Scanned over time by ``RecurrentActorCritic``
    (params broadcast, so the step and sequence paths share weights).
    """

    features: int

    @nn.compact
    def __call__(self, carry, xs):
        z, reset = xs
        c, h = carry
        keep = (1.0 - reset)[..., None].astype(c.dtype)
        carry = (c * keep, h * keep)
        # The cell runs in f32 regardless of the torso's compute dtype:
        # the carry is train-state (its dtype must be invariant across
        # scan steps and checkpoints), and at 128-256 units the cell is
        # a negligible share of the policy's FLOPs.
        carry, y = nn.OptimizedLSTMCell(self.features, name="cell")(
            carry, z.astype(jnp.float32)
        )
        return carry, y


class RecurrentActorCritic(nn.Module):
    """Recurrent (LSTM) policy + value heads over any discrete torso —
    the IMPALA/R2D2-era recurrent model family for partially observable
    tasks (e.g. velocity-masked CartPole, flicker Atari).

    Time-major sequence API: ``__call__(obs, resets, carry)`` with
    ``obs [T, B, ...]``, ``resets [T, B]`` (1.0 where step t begins a
    new episode — i.e. the previous step ended one), and ``carry`` a
    ``(c, h)`` pair of ``[B, lstm_size]`` arrays. Returns
    ``(logits [T, B, A], values [T, B], new_carry)``. Single-step use
    (collection, eval) is the same call with ``T == 1``; both paths
    share parameters because the scan broadcasts them.

    The torso runs batched over all ``T * B`` observations in one call
    (conv/MLP compute stays MXU-shaped); only the LSTM recurrence scans
    over time.
    """

    num_actions: int
    torso: str = "mlp"
    hidden_sizes: Sequence[int] = (64, 64)
    lstm_size: int = 128
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs, resets, carry):
        z = _discrete_torso(self.torso, self.hidden_sizes, self.dtype)(obs)
        scan = nn.scan(
            _MaskedLSTMCell,
            variable_broadcast="params",
            split_rngs={"params": False},
            in_axes=0,
            out_axes=0,
        )(self.lstm_size, name="lstm")
        carry, y = scan(carry, (z, resets))
        y = y.astype(self.dtype)
        logits = nn.Dense(
            self.num_actions, kernel_init=_orthogonal(0.01), dtype=self.dtype
        )(y)
        value = nn.Dense(1, kernel_init=_orthogonal(1.0), dtype=self.dtype)(y)
        return (
            logits.astype(jnp.float32),
            value[..., 0].astype(jnp.float32),
            carry,
        )

    def initialize_carry(self, batch: int):
        """Zero ``(c, h)`` carry for ``batch`` environments."""
        shape = (batch, self.lstm_size)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))


class DiscreteActorCritic(nn.Module):
    """Shared-torso policy + value heads for discrete action spaces.

    ``torso='mlp'`` gives the CartPole 2-layer MLP (BASELINE.json:7);
    ``torso='nature_cnn'`` the Atari encoder (BASELINE.json:8);
    ``torso='frame_transformer'`` the attention-over-frame-history
    encoder backed by ring attention.
    """

    num_actions: int
    torso: str = "mlp"
    hidden_sizes: Sequence[int] = (64, 64)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        z = _discrete_torso(self.torso, self.hidden_sizes, self.dtype)(obs)
        logits = nn.Dense(
            self.num_actions, kernel_init=_orthogonal(0.01), dtype=self.dtype
        )(z)
        value = nn.Dense(1, kernel_init=_orthogonal(1.0), dtype=self.dtype)(z)
        return logits.astype(jnp.float32), value[..., 0].astype(jnp.float32)


class GaussianActorCritic(nn.Module):
    """Continuous-control stochastic policy + value head (PPO on MuJoCo).

    State-independent log_std parameter, per standard continuous PPO.
    """

    action_dim: int
    hidden_sizes: Sequence[int] = (64, 64)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        z = MLPTorso(self.hidden_sizes, dtype=self.dtype)(obs)
        mean = nn.Dense(
            self.action_dim, kernel_init=_orthogonal(0.01), dtype=self.dtype
        )(z)
        log_std = self.param(
            "log_std", nn.initializers.zeros, (self.action_dim,)
        )
        zv = MLPTorso(self.hidden_sizes, dtype=self.dtype)(obs)
        value = nn.Dense(1, kernel_init=_orthogonal(1.0), dtype=self.dtype)(zv)
        return (
            mean.astype(jnp.float32),
            jnp.broadcast_to(log_std, mean.shape).astype(jnp.float32),
            value[..., 0].astype(jnp.float32),
        )


class DeterministicActor(nn.Module):
    """DDPG actor: tanh-bounded deterministic policy (BASELINE.json:9)."""

    action_dim: int
    hidden_sizes: Sequence[int] = (256, 256)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        z = MLPTorso(self.hidden_sizes, activation=nn.relu, dtype=self.dtype)(obs)
        a = nn.Dense(
            self.action_dim,
            kernel_init=_symmetric_uniform(3e-3),
            dtype=self.dtype,
        )(z)
        return jnp.tanh(a).astype(jnp.float32)


class QCritic(nn.Module):
    """State-action value function Q(s, a)."""

    hidden_sizes: Sequence[int] = (256, 256)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs, action):
        x = jnp.concatenate(
            [obs.astype(self.dtype), action.astype(self.dtype)], axis=-1
        )
        z = MLPTorso(self.hidden_sizes, activation=nn.relu, dtype=self.dtype)(x)
        q = nn.Dense(1, kernel_init=_symmetric_uniform(3e-3), dtype=self.dtype)(z)
        return q[..., 0].astype(jnp.float32)


class TwinQCritic(nn.Module):
    """Two independent Q networks evaluated in one call (SAC twin-Q,
    BASELINE.json:10). Returns ``(q1, q2)``."""

    hidden_sizes: Sequence[int] = (256, 256)
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs, action):
        q1 = QCritic(self.hidden_sizes, dtype=self.dtype)(obs, action)
        q2 = QCritic(self.hidden_sizes, dtype=self.dtype)(obs, action)
        return q1, q2


class SquashedGaussianActor(nn.Module):
    """SAC actor: tanh-squashed Gaussian with state-dependent std
    (BASELINE.json:10). Returns ``(mean, log_std)`` of the pre-tanh
    Gaussian; squashing/log-prob correction lives in
    ``ops.distributions.TanhGaussian``."""

    action_dim: int
    hidden_sizes: Sequence[int] = (256, 256)
    log_std_min: float = -20.0
    log_std_max: float = 2.0
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, obs):
        z = MLPTorso(self.hidden_sizes, activation=nn.relu, dtype=self.dtype)(obs)
        mean = nn.Dense(self.action_dim, dtype=self.dtype)(z)
        log_std = nn.Dense(self.action_dim, dtype=self.dtype)(z)
        log_std = jnp.clip(
            log_std.astype(jnp.float32), self.log_std_min, self.log_std_max
        )
        return mean.astype(jnp.float32), log_std
