"""On-policy trajectory containers.

Capability parity: the reference stores rollouts for its on-policy
trainers (BASELINE.json:5 — "the rollout/replay buffer lives in TPU
HBM"). In the Anakin design the rollout buffer IS the stacked output
of the collection ``lax.scan`` — a time-major ``Trajectory`` pytree
that never leaves HBM; these helpers name its fields and reshape it
for minibatched updates.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class Trajectory(NamedTuple):
    """Time-major rollout: every field is ``[T, B, ...]``."""

    obs: Any
    actions: jax.Array
    rewards: jax.Array
    dones: jax.Array
    log_probs: jax.Array
    values: jax.Array


def flatten_time_batch(tree):
    """[T, B, ...] -> [T*B, ...] for minibatched updates."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((-1,) + x.shape[2:]), tree
    )


def minibatch_iter_indices(key: jax.Array, n: int, num_minibatches: int):
    """Random permutation of ``n`` split into ``num_minibatches`` index
    blocks, as a ``[num_minibatches, n // num_minibatches]`` array."""
    perm = jax.random.permutation(key, n)
    size = n // num_minibatches
    return perm[: size * num_minibatches].reshape(num_minibatches, size)


def take_minibatch(tree, idx: jax.Array):
    return jax.tree_util.tree_map(lambda x: jnp.take(x, idx, axis=0), tree)


def env_block_starts(key: jax.Array, num_minibatches: int, block_envs: int):
    """Start offsets of contiguous env blocks, visit order permuted.

    The gather-free minibatch schedule (``PPOConfig.shuffle="env"``):
    the env axis is partitioned into ``num_minibatches`` CONTIGUOUS
    blocks of ``block_envs`` envs — each minibatch is every rollout
    step of one block (block ``start // block_envs`` of ``env_blocks``)
    instead of a full-buffer random gather — and only the ORDER the
    blocks are visited in is drawn per epoch. Env order is
    exchangeable (independent env instances), so a fixed contiguous
    partition is as unbiased as a random one; the permuted visit order
    still decorrelates the SGD sequence across epochs. Returns
    ``[num_minibatches]`` int32 starts.
    """
    return jax.random.permutation(key, num_minibatches) * block_envs


def env_blocks(x: jax.Array, num_blocks: int) -> jax.Array:
    """``[T, B, ...] -> [num_blocks, T * B/num_blocks, ...]``, block-major.

    Block ``i`` holds every rollout step of envs ``i*mb .. (i+1)*mb - 1``
    (``mb = B / num_blocks``), its samples in the ``t``-major order of
    ``x[:, i*mb:(i+1)*mb].reshape(T * mb, ...)``. An env-sliced
    minibatch (``env_block_starts``) is then an index on the leading
    axis; arranged once an iteration, no minibatch moves data of its own.
    """
    t, b = x.shape[:2]
    x = x.reshape((t, num_blocks, b // num_blocks) + x.shape[2:])
    return jnp.moveaxis(x, 1, 0).reshape((num_blocks, -1) + x.shape[3:])


def frame_storage_context(obs0, frames, dones, num_stack: int):
    """Context for stack-free rollout storage of frame-stacked obs.

    Frame-stacked image rollouts are ``num_stack``-fold redundant: the
    stack at step t shares ``num_stack - 1`` frames with step t-1. With
    ``AutoReset(FrameStack(env))`` semantics (reset step's stack is its
    first frame repeated), the full stack is reconstructible from the
    newest frame per step — a ``num_stack``x HBM saving on the rollout
    buffer, the enabler for very large env counts.

    Args:
      obs0: ``[B, H, W, num_stack*c]`` the stack entering the rollout.
      frames: ``[T, B, H, W, c]`` newest frame per step (step 0's equals
        ``obs0``'s last ``c`` channels).
      dones: ``[T, B]`` episode-boundary flags (``dones[t]=1`` means the
        step-``t+1`` stack is a fresh episode's repeated first frame).
      num_stack: stack depth s.

    Returns:
      ``(extended, resets)``: ``extended`` is ``[T+s-1, B, H, W, c]``
      holding frames for times ``-(s-1)..T-1`` (history from ``obs0``),
      ``resets`` is ``[T, B]`` int32, the latest reset step <= t (or
      ``-(s-1)`` when none) — the clamp floor for stack channels.
    """
    s = num_stack
    c = frames.shape[-1]
    hist = obs0[..., : (s - 1) * c]
    hist = hist.reshape(obs0.shape[:-1] + (s - 1, c))
    hist = jnp.moveaxis(hist, -2, 0)  # [s-1, B, H, W, c]
    extended = jnp.concatenate([hist, frames], axis=0)

    t_idx = jnp.arange(frames.shape[0])[:, None]
    reset_at = jnp.where(dones > 0.5, t_idx + 1, -(s - 1))
    resets = jax.lax.cummax(
        jnp.concatenate(
            [jnp.full((1, dones.shape[1]), -(s - 1)), reset_at[:-1]], axis=0
        ).astype(jnp.int32),
        axis=0,
    )
    return extended, resets


def gather_stacked_obs(extended, resets_flat, idx, num_envs: int, num_stack: int):
    """Rebuild ``[n, H, W, num_stack*c]`` stacks for flat sample indices.

    ``idx`` indexes the ``[T*B]`` flattening (``flat = t * B + b``);
    ``resets_flat`` is ``frame_storage_context``'s resets flattened the
    same way. Exactly inverts the compact storage: channel k of sample
    (t, b) is ``extended[max(t - (s-1) + k, resets[t, b]) + (s-1), b]``.
    """
    s = num_stack
    t = idx // num_envs
    b = idx % num_envs
    floor = resets_flat[idx]
    chans = []
    for k in range(s):
        j = jnp.maximum(t - (s - 1) + k, floor) + (s - 1)
        chans.append(extended[j, b])
    return jnp.concatenate(chans, axis=-1)
