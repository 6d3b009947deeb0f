"""Block turns: a multi-turn token environment for policies that
generate by diffusion over blocks, in which a step is one PASS of the
model over a block of ``block_length`` positions and not one token.

An episode is ``turns`` turns; a turn is ``denoise_steps + 2`` steps
and commits two blocks:

1. the env's block (1 step): the observation is ``block_length`` ids
   drawn from ``fold_in(key, turn)`` — a clean block, the turn's
   "prompt", which the policy's pass writes into its cache (a commit
   pass); the action is ignored, the reward 0.
2. the policy's block (``denoise_steps`` steps): the observation is the
   block under denoising, all ``mask_id`` at first; the action is the
   block after the pass, of which the env keeps ``where(block == mask,
   action, block)``. The reward is the number of positions revealed in
   this step whose id equals the id at the same index of the env's
   block ``delay_turns`` turns earlier (0 before turn ``delay_turns``).
   After the last denoising step a position still masked is filled with
   id 0, so the next observation is always clean (under a sampler that
   reveals ``block_length / denoise_steps`` positions a pass it never
   happens; it keeps the tokens committed a function of the schedule).
3. the commit of the policy's own block (1 step): the observation is
   the finished block as the policy made it; action ignored, reward 0.

Only what the policy carries in its cache of committed blocks can earn
the reward, as in ``token_recall.py``: the delay lies ``delay_turns``
committed turns back. One episode is one sequence of ``episode_length =
turns * (denoise_steps + 2)`` steps that commits ``tokens_per_episode =
turns * 2 * block_length`` tokens, then ``truncated``.

The state is the episode's key, the step and the block shown; an env
block is drawn from ``fold_in(key, turn)``, so nothing is stored to
look back.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from flax import struct

from actor_critic_algs_on_tensorflow_tpu.envs.core import JaxEnv, TokenBlock


@struct.dataclass
class BlockTurnsParams:
    vocab_size: int = struct.field(pytree_node=False, default=18992)
    block_length: int = struct.field(pytree_node=False, default=4)
    denoise_steps: int = struct.field(pytree_node=False, default=4)
    turns: int = struct.field(pytree_node=False, default=24)
    delay_turns: int = struct.field(pytree_node=False, default=4)

    @property
    def mask_id(self) -> int:
        """The last id of the vocabulary; tokens come from below it."""
        return self.vocab_size - 1

    @property
    def steps_per_turn(self) -> int:
        return self.denoise_steps + 2

    @property
    def episode_length(self) -> int:
        return self.turns * self.steps_per_turn

    @property
    def tokens_per_episode(self) -> int:
        return self.turns * 2 * self.block_length


@struct.dataclass
class BlockTurnsState:
    key: jax.Array    # the episode's key
    t: jax.Array      # step within the episode (int32)
    block: jax.Array  # the block shown [block_length] (int32)


def _env_block(key, turn, params: BlockTurnsParams):
    return jax.random.randint(
        jax.random.fold_in(key, turn), (params.block_length,), 0,
        params.mask_id, jnp.int32,
    )


class BlockTurns(JaxEnv[BlockTurnsState, BlockTurnsParams]):
    name = "BlockTurnsTPU-v0"

    def default_params(self) -> BlockTurnsParams:
        return BlockTurnsParams()

    def reset(self, key, params):
        t = jnp.zeros((), jnp.int32)
        state = BlockTurnsState(key=key, t=t, block=_env_block(key, t, params))
        return state, state.block

    def step(self, key, state, action, params):
        del key
        mask, last = params.mask_id, params.denoise_steps
        turn, phase = jnp.divmod(state.t, params.steps_per_turn)
        block = state.block
        # a denoising step: the env keeps what the pass revealed
        kept = jnp.where(block == mask, action.astype(jnp.int32), block)
        revealed = (block == mask) & (kept != mask)
        target = _env_block(
            state.key, jnp.maximum(turn - params.delay_turns, 0), params
        )
        reward = jnp.where(
            (phase >= 1) & (phase <= last) & (turn >= params.delay_turns),
            jnp.sum(revealed & (kept == target)), 0,
        ).astype(jnp.float32)
        finished = jnp.where(kept == mask, 0, kept)
        block = jnp.select(
            [phase == 0, phase < last, phase == last],
            [jnp.full_like(block, mask), kept, finished],
            _env_block(state.key, turn + 1, params),
        )
        state = BlockTurnsState(key=state.key, t=state.t + 1, block=block)
        truncated = (state.t >= params.episode_length).astype(jnp.float32)
        info: Dict[str, jax.Array] = {
            "terminated": jnp.zeros((), jnp.float32),
            "truncated": truncated,
        }
        return state, block, reward, truncated, info

    def observation_space(self, params):
        return TokenBlock(params.vocab_size, params.block_length,
                          params.mask_id)

    def action_space(self, params):
        return self.observation_space(params)
