"""Host-resident gymnasium environments bridged into jitted programs.

Capability parity: the reference steps real Gym environments — MuJoCo
HalfCheetah-v4 and Humanoid-v4 for DDPG/SAC (BASELINE.json:9,10) —
from its Python training loop. TPU-first, physics simulators cannot
move on-device, so the bridge goes the other way: the host vector env
is called FROM INSIDE the jitted rollout scan via
``jax.experimental.io_callback`` (ordered), so the same fused
collect+learn iteration programs (algos.common / algos.offpolicy) run
unchanged over host envs — only the env object differs
(SURVEY.md L2: "host-side env stepping bridged into the TPU program").

The JAX-side ``EnvState`` is a step-counter token; the real state
(simulator, per-episode stats) lives host-side in this object. The
vector env uses gymnasium's SAME_STEP autoreset, matching the
on-device ``AutoReset`` wrapper convention exactly: at a done step the
returned obs is the NEW episode's first observation and
``info["final_obs"]`` is the pre-reset observation (for time-limit
bootstrapping). ``info`` carries the same keys as the pure-JAX wrapper
stack (episode_return / episode_length / done_episode / terminated /
truncated / final_obs), so trainers cannot tell the difference.

Concurrency: ``backend="async"`` runs each env in its own process
(gymnasium AsyncVectorEnv + shared memory), the host analog of the
reference's parallel actors; ``"sync"`` steps in-process.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.experimental import io_callback

from actor_critic_algs_on_tensorflow_tpu.envs.core import Box, Discrete, JaxEnv


@struct.dataclass
class HostEnvState:
    """Ordering token; the simulator itself lives on the host."""

    t: jax.Array  # int32 step counter


def step_via_callback(host_step, num_envs: int, obs_shape, action):
    """``(obs, reward, done, info)`` of one bridged env step.

    ``host_step(action)`` is the host-side contract both bridges
    implement: eight float32 arrays ``(obs, reward, done, terminated,
    truncated, final_obs, episode_return, episode_length)``. They cross
    into the jitted program through ONE ordered ``io_callback`` with
    ONE packed ``[num_envs, 2 * obs_size + 6]`` result, unpacked here.

    One result, not eight: the TPU lowering emits one host->device
    ``recv`` per callback result and (jax 0.9.0, Shardy) stamps every
    one of them with the FIRST result's sharding, so results of
    different ranks — obs ``[n, d]`` beside reward ``[n]`` — fail MLIR
    verification ("sharding doesn't match tensor rank"). It is also one
    transfer per env step instead of eight.
    """
    obs_shape = tuple(obs_shape)
    obs_size = int(np.prod(obs_shape))

    def packed_step(action):
        (obs, reward, done, term, trunc, final_obs,
         ep_ret, ep_len) = host_step(action)
        return np.concatenate(
            [
                np.reshape(obs, (num_envs, obs_size)),
                np.reshape(final_obs, (num_envs, obs_size)),
                np.stack(
                    [reward, done, term, trunc, ep_ret, ep_len], axis=1
                ),
            ],
            axis=1,
            dtype=np.float32,
        )

    packed = io_callback(
        packed_step,
        jax.ShapeDtypeStruct((num_envs, 2 * obs_size + 6), jnp.float32),
        action,
        ordered=True,
    )
    obs = packed[:, :obs_size].reshape((num_envs,) + obs_shape)
    final_obs = packed[:, obs_size:2 * obs_size].reshape(
        (num_envs,) + obs_shape
    )
    reward, done, term, trunc, ep_ret, ep_len = (
        packed[:, 2 * obs_size + i] for i in range(6)
    )
    info = {
        "terminated": term,
        "truncated": trunc,
        "final_obs": final_obs,
        "episode_return": ep_ret,
        "episode_length": ep_len,
        "done_episode": done,
    }
    return obs, reward, done, info


def _atari_ctor(env_id: str):
    """Constructor for real-ALE Atari ids (``ALE/Pong-v5``,
    ``PongNoFrameskip-v4``), or None for non-Atari ids.

    Parity target: the reference's PPO Atari workload runs real
    ``PongNoFrameskip-v4`` (BASELINE.json:8). This image has no
    ``ale_py`` wheel and no network, so the shipped Atari presets use
    the on-device clones — but the host bridge serves real ALE
    wherever ``ale_py`` exists: standard DeepMind preprocessing
    (frame-skip 4 with max-pooling, grayscale, 84x84, SCALED to
    [0, 1] — the bridge's obs contract is float32, and NatureCNN
    only rescales uint8 inputs) + 4-frame stacking, emitted
    channels-last so the Nature-CNN torso consumes the same [0, 1]
    84x84x4 layout as the on-device envs (at 4 bytes/pixel over the
    host->HBM hop, the float32 bridge contract).
    """
    if not (env_id.startswith("ALE/") or "NoFrameskip" in env_id):
        return None

    def ctor():
        import gymnasium as gym
        import numpy as np

        try:
            import ale_py

            gym.register_envs(ale_py)
        except ImportError as exc:
            raise RuntimeError(
                f"env {env_id!r} needs the Arcade Learning Environment "
                "(pip install ale-py), which is not available in this "
                "image. The on-device Atari-class envs (PongTPU-v0, "
                "BreakoutTPU-v0) cover the same workloads without a "
                "host dependency."
            ) from exc

        env = gym.make(env_id, frameskip=1)
        env = gym.wrappers.AtariPreprocessing(
            env, frame_skip=4, grayscale_obs=True, screen_size=84,
            scale_obs=True,
        )
        env = gym.wrappers.FrameStackObservation(env, 4)

        class _ChannelsLast(gym.ObservationWrapper):
            def __init__(self, inner):
                super().__init__(inner)
                shp = inner.observation_space.shape  # [4, 84, 84]
                self.observation_space = gym.spaces.Box(
                    0.0, 1.0, (shp[1], shp[2], shp[0]), np.float32
                )

            def observation(self, obs):
                return np.moveaxis(np.asarray(obs, np.float32), 0, -1)

        return _ChannelsLast(env)

    return ctor


class HostGymEnv(JaxEnv):
    """A gymnasium vector env exposed through the functional JaxEnv API.

    NOT pure: reset/step mutate the host simulator via ``io_callback``.
    Use a single-device mesh (``num_devices=1``) — host envs cannot be
    sharded across devices from one process. ``num_envs`` parallel env
    instances still vectorize acting/learning on the chip.
    """

    def __init__(
        self,
        env_id: str,
        num_envs: int,
        *,
        backend: str = "sync",
        seed: int = 0,
        **env_kwargs,
    ):
        import gymnasium as gym

        self.name = env_id
        self.num_envs = num_envs
        self._seed = seed
        ctor = (
            gym.vector.AsyncVectorEnv
            if backend == "async"
            else gym.vector.SyncVectorEnv
        )
        kwargs = dict(autoreset_mode=gym.vector.AutoresetMode.SAME_STEP)
        if backend == "async":
            kwargs["daemon"] = True
        make_one = _atari_ctor(env_id) or (
            lambda: gym.make(env_id, **env_kwargs)
        )
        self._env = ctor(
            [make_one for _ in range(num_envs)],
            **kwargs,
        )
        self._single_obs_space = self._env.single_observation_space
        self._single_act_space = self._env.single_action_space
        self._obs_shape = tuple(self._single_obs_space.shape)
        self._ep_return = np.zeros(num_envs, np.float32)
        self._ep_length = np.zeros(num_envs, np.float32)
        self._discrete = not hasattr(self._single_act_space, "high")

        self._reset_struct = jax.ShapeDtypeStruct(
            (num_envs,) + self._obs_shape, jnp.float32
        )

    # -- host-side impls ------------------------------------------------

    def _host_reset(self, seed):
        obs, _ = self._env.reset(seed=int(seed))
        self._ep_return[:] = 0.0
        self._ep_length[:] = 0.0
        return np.asarray(obs, np.float32)

    def _host_step(self, action):
        action = np.asarray(action)
        if self._discrete:
            action = action.astype(self._single_act_space.dtype)
        obs, reward, term, trunc, info = self._env.step(action)
        obs = np.asarray(obs, np.float32)
        reward = np.asarray(reward, np.float32)
        done = (term | trunc).astype(np.float32)
        self._ep_return += reward
        self._ep_length += 1.0
        ep_return = self._ep_return.copy()
        ep_length = self._ep_length.copy()
        final_obs = obs
        if done.any():
            final_obs = obs.copy()
            fo = info.get("final_obs")
            if fo is not None:
                mask = info.get("_final_obs", done > 0.5)
                for i in np.nonzero(mask)[0]:
                    final_obs[i] = np.asarray(fo[i], np.float32)
            self._ep_return[done > 0.5] = 0.0
            self._ep_length[done > 0.5] = 0.0
        return (
            obs,
            reward,
            done,
            term.astype(np.float32),
            trunc.astype(np.float32),
            final_obs,
            ep_return,
            ep_length,
        )

    # -- functional API -------------------------------------------------

    def default_params(self):
        return None

    def reset(self, key: jax.Array, params=None) -> Tuple[HostEnvState, jax.Array]:
        seed = jax.random.randint(key, (), 0, np.iinfo(np.int32).max)
        obs = io_callback(
            self._host_reset, self._reset_struct, seed, ordered=True
        )
        return HostEnvState(t=jnp.zeros((), jnp.int32)), obs

    def step(self, key: jax.Array, state: HostEnvState, action, params=None):
        obs, reward, done, info = step_via_callback(
            self._host_step, self.num_envs, self._obs_shape, action
        )
        return HostEnvState(t=state.t + 1), obs, reward, done, info

    def observation_space(self, params=None):
        return Box(
            float(np.min(self._single_obs_space.low)),
            float(np.max(self._single_obs_space.high)),
            self._obs_shape,
            jnp.float32,
        )

    def action_space(self, params=None):
        sp = self._single_act_space
        if self._discrete:
            return Discrete(int(sp.n))
        return Box(
            float(np.min(sp.low)),
            float(np.max(sp.high)),
            tuple(sp.shape),
            jnp.float32,
        )

    def close(self):
        self._env.close()
