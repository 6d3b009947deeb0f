"""Token recall: a token-level environment for sequence policies.

Every step shows a token drawn uniformly from the vocabulary; the
action is a token, and it earns 1 where it equals the token shown
``delay`` steps earlier. The delay (64 by default) lies far beyond a
short convolution's reach, so only a policy that carries what it saw —
a recurrent state, a key/value cache — can earn it: the task is the
memory. One episode is one sequence of ``episode_length`` steps, as in
RL fine-tuning of a language model against a programmatic reward (one
action a token, one episode a sequence).

The state is the episode's key and the step: the token of step ``t``
is drawn from ``fold_in(key, t)``, so nothing is stored to look back.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from flax import struct

from actor_critic_algs_on_tensorflow_tpu.envs.core import Discrete, JaxEnv


@struct.dataclass
class TokenRecallParams:
    vocab_size: int = struct.field(pytree_node=False, default=18992)
    delay: int = struct.field(pytree_node=False, default=64)
    episode_length: int = struct.field(pytree_node=False, default=256)


@struct.dataclass
class TokenRecallState:
    key: jax.Array  # the episode's key
    t: jax.Array    # step within the episode (int32)


def _token(key, t, params: TokenRecallParams):
    return jax.random.randint(
        jax.random.fold_in(key, t), (), 0, params.vocab_size, jnp.int32
    )


class TokenRecall(JaxEnv[TokenRecallState, TokenRecallParams]):
    name = "TokenRecallTPU-v0"

    def default_params(self) -> TokenRecallParams:
        return TokenRecallParams()

    def reset(self, key, params):
        state = TokenRecallState(key=key, t=jnp.zeros((), jnp.int32))
        return state, _token(key, state.t, params)

    def step(self, key, state, action, params):
        del key
        t = state.t
        target = _token(state.key, jnp.maximum(t - params.delay, 0), params)
        reward = (
            (t >= params.delay) & (action.astype(jnp.int32) == target)
        ).astype(jnp.float32)
        state = TokenRecallState(key=state.key, t=t + 1)
        truncated = (state.t >= params.episode_length).astype(jnp.float32)
        info: Dict[str, jax.Array] = {
            "terminated": jnp.zeros((), jnp.float32),
            "truncated": truncated,
        }
        return (state, _token(state.key, state.t, params), reward,
                truncated, info)

    def observation_space(self, params):
        return Discrete(params.vocab_size)

    def action_space(self, params):
        return Discrete(params.vocab_size)
