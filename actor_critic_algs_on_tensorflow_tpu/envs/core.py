"""Functional environment API for on-device (pure-JAX) environments.

Capability parity: the reference steps Gym environments from Python
(BASELINE.json:7-10). A TPU-first design inverts this where possible:
environments whose dynamics are a few dozen FLOPs (CartPole,
Pendulum, a Pong-class board game) are implemented as pure JAX
functions, so the entire rollout loop — policy forward, env step,
storage — compiles into ONE ``lax.scan`` on device (the "Anakin"
architecture, Hessel et al. 2021) and never round-trips to the host.
Host-resident envs (MuJoCo) will use the host bridge (``envs.host``,
added with the DDPG/SAC milestone) instead.

API: an environment is a stateless object with pure methods

    reset(key, params)           -> (EnvState, obs)
    step(key, state, action, params) -> (EnvState, obs, reward, done, info)

``done`` is 1.0 at terminal OR truncation boundaries; ``info`` carries
``terminated``/``truncated`` separately (gymnasium semantics) so value
bootstrapping can distinguish them.
"""

from __future__ import annotations

from typing import Any, Dict, Generic, Tuple, TypeVar

import jax
import jax.numpy as jnp
from flax import struct

TEnvState = TypeVar("TEnvState")
TParams = TypeVar("TParams")


@struct.dataclass
class Box:
    """Continuous space with a static shape."""

    low: float
    high: float
    shape: Tuple[int, ...] = struct.field(pytree_node=False, default=())
    dtype: Any = struct.field(pytree_node=False, default=jnp.float32)

    def sample(self, key: jax.Array) -> jax.Array:
        return jax.random.uniform(
            key, self.shape, self.dtype, self.low, self.high
        )


@struct.dataclass
class Discrete:
    """Discrete space {0, ..., n-1}."""

    n: int = struct.field(pytree_node=False, default=2)

    def sample(self, key: jax.Array) -> jax.Array:
        return jax.random.randint(key, (), 0, self.n)


@struct.dataclass
class TokenBlock:
    """Blocks of ``block_length`` token ids of ``{0, ..., n-1}``,
    ``mask_token_id`` marking a position not yet revealed: what a
    policy that generates by diffusion over blocks observes and hands
    back (field names as ``models/sdar.py::SDARConfig`` has them)."""

    n: int = struct.field(pytree_node=False, default=2)
    block_length: int = struct.field(pytree_node=False, default=1)
    mask_token_id: int = struct.field(pytree_node=False, default=1)

    def sample(self, key: jax.Array) -> jax.Array:
        return jax.random.randint(key, (self.block_length,), 0, self.n)


class JaxEnv(Generic[TEnvState, TParams]):
    """Base class for pure-functional environments."""

    name: str = "JaxEnv"

    def default_params(self) -> TParams:
        raise NotImplementedError

    def reset(self, key: jax.Array, params: TParams) -> Tuple[TEnvState, jax.Array]:
        raise NotImplementedError

    def step(
        self,
        key: jax.Array,
        state: TEnvState,
        action: jax.Array,
        params: TParams,
    ) -> Tuple[TEnvState, jax.Array, jax.Array, jax.Array, Dict[str, jax.Array]]:
        raise NotImplementedError

    def observation_space(self, params: TParams):
        raise NotImplementedError

    def action_space(self, params: TParams):
        raise NotImplementedError
