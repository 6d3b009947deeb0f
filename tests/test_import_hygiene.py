"""Importing the package must not initialize the jax backend.

Platform selection via ``jax.config.update("jax_platforms", ...)`` —
which the CLI's ``--platform`` flag, the actor/shim/replay child mains
(``parallel.mesh.pin_process_to_cpu``) and the CPU bench legs use —
only works while the backend is still uninitialized. Any module-level
``jnp.asarray(...)`` / ``jnp.sqrt(...)`` constant eagerly creates a
device buffer, locks the platform choice, and on the chip machine
(whose environment names the TPU first) would land a CPU-only child on
the chip its parent holds.
"""

import os
import subprocess
import sys

_PROBE = """
import jax
import actor_critic_algs_on_tensorflow_tpu
import actor_critic_algs_on_tensorflow_tpu.cli.train
import sys
# the presets build every sequence core's config: their modules, the
# block-diffusion core and its env among them, are imported by now
for module in ("models.qwen3_next", "models.kimi_vl", "models.sdar",
               "models.granite_hybrid", "envs.block_turns"):
    assert "actor_critic_algs_on_tensorflow_tpu." + module in sys.modules
# Behavioral probe (public API only): selecting a platform after the
# package import only takes effect while the backend is still
# uninitialized — if any module eagerly created a device buffer, the
# environment's platform wins instead of cpu.
jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", jax.devices()
print("LAZY_OK")
"""


def test_package_import_leaves_backend_uninitialized():
    # A fresh interpreter WITHOUT the conftest's JAX_PLATFORMS=cpu
    # os.environ mutation (which the child would otherwise inherit and
    # trivially satisfy the cpu assertion): drop the variable so the
    # child sees only the environment's own platform choice, the state
    # in which --platform must still win.
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True,
        text=True,
        timeout=180,
        env=env,
    )
    assert out.returncode == 0, out.stderr
    assert "LAZY_OK" in out.stdout, (out.stdout, out.stderr)


def test_the_cli_import_does_not_pay_for_pallas():
    """``cli/train.py``'s PRESETS import ``models/qwen3_next.py`` for
    every preset, and a benchmark cell's ``setup_s`` counts the import:
    the ~1.5 s of Pallas imports belong to the one program that runs
    the kernel (``qwen3_next._state_step`` imports it where it is
    used), not to each of them."""
    probe = (
        "import sys\n"
        "import actor_critic_algs_on_tensorflow_tpu.cli.train\n"
        "assert 'jax.experimental.pallas' not in sys.modules\n"
        "print('NO_PALLAS')\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True,
        timeout=180, env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, out.stderr
    assert "NO_PALLAS" in out.stdout, (out.stdout, out.stderr)
