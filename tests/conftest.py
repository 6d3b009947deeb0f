"""Test configuration: an 8-device virtual CPU mesh.

Per SURVEY.md §4.3: distributed behavior is tested without a TPU pod by
faking 8 host devices in one process. ``JAX_PLATFORMS=cpu`` in the
environment works too; the suite selects the platform through
``jax.config`` so it runs the same whatever the environment says.
"""

import os

# Children the tests spawn (actor processes, CLI subprocesses) inherit
# the platform through the environment.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

# The suite is compile-bound (every mesh test pays XLA compilation on
# 8 virtual devices); the persistent compilation cache makes warm runs
# fast. Same placement rule as every program path: see compile_cache.
compile_cache.enable()

assert len(jax.devices()) == 8, jax.devices()
