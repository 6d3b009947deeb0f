"""ctypes bridge to the native C++ env pool (native/envpool.cpp).

Capability parity: the reference's env stepping bottoms out in native
code inside its dependencies (SURVEY.md §2.3); here the framework owns
that layer — a C++ thread-pool env stepper compiled on first use and
driven through the same ordered-``io_callback`` contract as the
gymnasium bridge, so trainers are agnostic to which backend produced
the batch. Use ``native:CartPole-v1`` / ``native:Pendulum-v1`` env ids.

The shared library is built with g++ (no pip deps) on first use into
``native/build/`` (not tracked), under a name that carries the hash of
``envpool.cpp``'s content: a checkout or copy with different source
builds its own library, and one with the same source reuses it.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.experimental import io_callback

from actor_critic_algs_on_tensorflow_tpu.envs.core import Box, Discrete, JaxEnv
from actor_critic_algs_on_tensorflow_tpu.envs.host import step_via_callback

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_SRC = os.path.join(_REPO_ROOT, "native", "envpool.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_BUILD_LOCK = threading.Lock()
_LIB = None


def _lib_path() -> str:
    """Library path for the CURRENT content of ``envpool.cpp``."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_BUILD_DIR, f"libenvpool-{digest}.so")


def _compile(lib_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Build beside the target and rename: actor processes that race to
    # the first build each publish a complete library or none.
    tmp_path = f"{lib_path}.{os.getpid()}.tmp"
    proc = subprocess.run(
        [
            "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
            "-pthread", _SRC, "-o", tmp_path,
        ],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native envpool build failed "
            f"(exit {proc.returncode}):\n{proc.stderr}"
        )
    os.replace(tmp_path, lib_path)


def _load_library() -> ctypes.CDLL:
    """Build (when no library exists for this source) and load the
    native pool. A library that fails to load raises."""
    global _LIB
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        lib_path = _lib_path()
        if not os.path.exists(lib_path):
            _compile(lib_path)
        lib = ctypes.CDLL(lib_path)
        lib.envpool_create.restype = ctypes.c_void_p
        lib.envpool_create.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
        ]
        for name in ("envpool_obs_dim", "envpool_action_dim",
                     "envpool_num_actions"):
            fn = getattr(lib, name)
            fn.restype = ctypes.c_int
            fn.argtypes = [ctypes.c_void_p]
        lib.envpool_action_high.restype = ctypes.c_float
        lib.envpool_action_high.argtypes = [ctypes.c_void_p]
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.envpool_reset.restype = None
        lib.envpool_reset.argtypes = [ctypes.c_void_p, ctypes.c_uint64, f32p]
        lib.envpool_step.restype = None
        lib.envpool_step.argtypes = [ctypes.c_void_p] + [f32p] * 9
        lib.envpool_destroy.restype = None
        lib.envpool_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
        return lib


def _fp(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


@struct.dataclass
class NativeEnvState:
    """Ordering token; the simulator lives in the C++ pool."""

    t: jax.Array


class NativeEnvPool(JaxEnv):
    """C++ thread-pool env exposed through the functional JaxEnv API.

    Same statefulness caveats as :class:`envs.host.HostGymEnv`: use a
    1-device mesh and one consumer per instance.
    """

    def __init__(
        self,
        env_id: str,
        num_envs: int,
        *,
        num_threads: int = 0,
        seed: int = 0,
    ):
        lib = _load_library()
        if num_threads <= 0:
            num_threads = min(num_envs, os.cpu_count() or 1)
        self._lib = lib
        self._handle = lib.envpool_create(
            env_id.encode(), num_envs, num_threads, seed
        )
        if not self._handle:
            raise KeyError(f"native env pool does not implement {env_id!r}")
        self.name = f"native:{env_id}"
        self.num_envs = num_envs
        self._obs_dim = lib.envpool_obs_dim(self._handle)
        self._action_dim = lib.envpool_action_dim(self._handle)
        self._num_actions = lib.envpool_num_actions(self._handle)
        self._action_high = float(lib.envpool_action_high(self._handle))
        self._reset_struct = jax.ShapeDtypeStruct(
            (num_envs, self._obs_dim), jnp.float32
        )

    # -- host-side impls ------------------------------------------------

    def _host_reset(self, seed):
        obs = np.empty((self.num_envs, self._obs_dim), np.float32)
        self._lib.envpool_reset(self._handle, int(seed), _fp(obs))
        return obs

    def _host_step(self, action):
        n, od = self.num_envs, self._obs_dim
        action = np.ascontiguousarray(
            np.asarray(action, np.float32).reshape(n, -1)
        )
        obs = np.empty((n, od), np.float32)
        final_obs = np.empty((n, od), np.float32)
        outs = [np.empty((n,), np.float32) for _ in range(6)]
        reward, done, term, trunc, ep_ret, ep_len = outs
        self._lib.envpool_step(
            self._handle, _fp(action), _fp(obs), _fp(reward), _fp(done),
            _fp(term), _fp(trunc), _fp(final_obs), _fp(ep_ret), _fp(ep_len),
        )
        return obs, reward, done, term, trunc, final_obs, ep_ret, ep_len

    # -- functional API -------------------------------------------------

    def default_params(self):
        return None

    def reset(self, key: jax.Array, params=None) -> Tuple[NativeEnvState, jax.Array]:
        seed = jax.random.randint(key, (), 0, np.iinfo(np.int32).max)
        obs = io_callback(
            self._host_reset, self._reset_struct, seed, ordered=True
        )
        return NativeEnvState(t=jnp.zeros((), jnp.int32)), obs

    def step(self, key: jax.Array, state: NativeEnvState, action, params=None):
        obs, reward, done, info = step_via_callback(
            self._host_step, self.num_envs, (self._obs_dim,), action
        )
        return NativeEnvState(t=state.t + 1), obs, reward, done, info

    def observation_space(self, params=None):
        return Box(-np.inf, np.inf, (self._obs_dim,), jnp.float32)

    def action_space(self, params=None):
        if self._action_dim == 0:
            return Discrete(self._num_actions)
        # Symmetric bound exported by the C ABI, next to the dynamics.
        high = self._action_high
        return Box(-high, high, (self._action_dim,), jnp.float32)

    def close(self):
        if self._handle:
            self._lib.envpool_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
