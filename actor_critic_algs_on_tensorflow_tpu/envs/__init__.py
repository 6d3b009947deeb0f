"""Environments: pure-JAX on-device envs + wrappers (+ host bridge).

``make(name, num_envs)`` builds the canonical wrapped/vectorized stack
for a named environment.
"""

from actor_critic_algs_on_tensorflow_tpu.envs.block_turns import (  # noqa: F401
    BlockTurns,
    BlockTurnsParams,
)
from actor_critic_algs_on_tensorflow_tpu.envs.breakout import (  # noqa: F401
    BreakoutParams,
    BreakoutTPU,
)
from actor_critic_algs_on_tensorflow_tpu.envs.cartpole import (  # noqa: F401
    CartPole,
    CartPoleMasked,
    CartPoleParams,
)
from actor_critic_algs_on_tensorflow_tpu.envs.core import (  # noqa: F401
    Box,
    Discrete,
    JaxEnv,
    TokenBlock,
)
from actor_critic_algs_on_tensorflow_tpu.envs.pendulum import (  # noqa: F401
    Pendulum,
    PendulumParams,
)
from actor_critic_algs_on_tensorflow_tpu.envs.pong import (  # noqa: F401
    PongFlickerParams,
    PongFlickerTPU,
    PongParams,
    PongServeTPU,
    PongTPU,
)
from actor_critic_algs_on_tensorflow_tpu.envs.reacher import (  # noqa: F401
    ReacherParams,
    ReacherTPU,
)
from actor_critic_algs_on_tensorflow_tpu.envs.synthetic import (  # noqa: F401
    SyntheticPixels,
    SyntheticPixelsParams,
    SyntheticPixelsSmall,
)
from actor_critic_algs_on_tensorflow_tpu.envs.token_recall import (  # noqa: F401
    TokenRecall,
    TokenRecallParams,
)
from actor_critic_algs_on_tensorflow_tpu.envs.wrappers import (  # noqa: F401
    AutoReset,
    EpisodeStats,
    FrameStack,
    VecEnv,
    Wrapper,
)

_REGISTRY = {
    "BlockTurnsTPU-v0": BlockTurns,
    "BreakoutTPU-v0": BreakoutTPU,
    "CartPole-v1": CartPole,
    "CartPoleMasked-v1": CartPoleMasked,
    "Pendulum-v1": Pendulum,
    "PongFlickerTPU-v0": PongFlickerTPU,
    "PongServeTPU-v0": PongServeTPU,
    "PongTPU-v0": PongTPU,
    "ReacherTPU-v0": ReacherTPU,
    "SyntheticPixels-v0": SyntheticPixels,
    "SyntheticPixelsSmall-v0": SyntheticPixelsSmall,
    "TokenRecallTPU-v0": TokenRecall,
}

def registered_names():
    """Sorted names of every registered pure-JAX env — the
    device-residentable set: each one's canonical wrapped stack is
    pinned jit+scan+shard_map-safe (tests/test_envs.py), so any of
    them can compile into the fused Anakin program
    (``ImpalaConfig.rollout_mode='device'``). Host-bridged ``gym:`` /
    ``native:`` envs are deliberately absent."""
    return sorted(_REGISTRY)


# Host envs are stateful (the simulator lives host-side), so repeated
# make() calls for the same (id, width) must share ONE instance — the
# trainers build a local-width and a global-width env and expect them
# to be the same pool on a 1-device mesh.
_HOST_CACHE = {}


def make(
    name: str,
    num_envs: int = 1,
    *,
    frame_stack: int = 0,
    params=None,
    fresh: bool = False,
):
    """Build ``VecEnv(EpisodeStats(AutoReset([FrameStack(env)])))`` for a
    registered pure-JAX env, or a cached :class:`HostGymEnv` for a
    ``gym:``-prefixed gymnasium id (e.g. ``gym:HalfCheetah-v4``).

    ``fresh=True`` bypasses the host-env cache, returning a private
    simulator pool — required when several independent consumers (e.g.
    IMPALA actor threads, or eval alongside training at the same width)
    would otherwise interleave steps on one shared pool.

    Returns ``(vec_env, params)``.
    """
    if name.startswith(("native:", "gym:")):
        # NOTE: backend host-callback support is checked at BRIDGE USE
        # (HostGymEnv/NativeEnvPool reset/step), not here — direct
        # host-side stepping (algos.host_async) needs no callbacks.
        if frame_stack and frame_stack > 1:
            raise ValueError(
                f"frame_stack is not supported on host-resident envs "
                f"({name!r}); wrap the underlying env instead"
            )
        if name.startswith("native:"):
            from actor_critic_algs_on_tensorflow_tpu.envs.native import (
                NativeEnvPool,
            )

            env_id = name[len("native:"):]
            key = ("native", env_id, num_envs)
            ctor = lambda: NativeEnvPool(env_id, num_envs)
        else:
            from actor_critic_algs_on_tensorflow_tpu.envs.host import (
                HostGymEnv,
            )

            env_id = name[len("gym:"):]
            backend = "sync"
            if env_id.startswith("async:"):
                env_id, backend = env_id[len("async:"):], "async"
            key = ("gym", env_id, num_envs, backend)
            ctor = lambda: HostGymEnv(env_id, num_envs, backend=backend)
        if fresh:
            return ctor(), None
        if key not in _HOST_CACHE:
            _HOST_CACHE[key] = ctor()
        return _HOST_CACHE[key], None
    if name not in _REGISTRY:
        raise KeyError(f"unknown env {name!r}; known: {sorted(_REGISTRY)}")
    env = _REGISTRY[name]()
    if params is None:
        params = env.default_params()
    if frame_stack and frame_stack > 1:
        env = FrameStack(env, frame_stack)
    env = VecEnv(EpisodeStats(AutoReset(env)), num_envs)
    return env, params
