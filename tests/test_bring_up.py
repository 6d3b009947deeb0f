"""What PR 21's bring-up changed about how the program finds its
device, places its compile cache and keeps children off the chip."""

import os
import subprocess
import sys

import jax
import pytest

from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, **env_changes):
    """Run a fresh interpreter from the repo root; ``None`` drops a
    variable from the child's environment."""
    env = dict(os.environ)
    for k, v in env_changes.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    argv = (
        [sys.executable, "-c", code_or_argv]
        if isinstance(code_or_argv, str) else
        [sys.executable] + code_or_argv
    )
    return subprocess.run(
        argv, capture_output=True, text=True, cwd=REPO, env=env,
        timeout=300,
    )


# -- the compile cache can be placed from outside ------------------------


def test_cache_dir_from_the_environment_is_not_set_in_code(
    monkeypatch, tmp_path
):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads the variable
    itself: the helper reports it and updates no directory in code."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    updated = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updated.append(name)
    )
    assert compile_cache.enable() == str(tmp_path / "cc")
    assert "jax_compilation_cache_dir" not in updated
    assert not (tmp_path / "cc").exists()  # nothing created up front


_PRINT_CACHE_DIR = (
    "import jax\n"
    "from actor_critic_algs_on_tensorflow_tpu.utils import compile_cache\n"
    "compile_cache.enable()\n"
    "print(jax.config.jax_compilation_cache_dir)\n"
)


def test_cache_dir_from_the_environment_wins_in_a_fresh_process(tmp_path):
    out = _run(
        _PRINT_CACHE_DIR, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc")
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path / "cc")


def test_default_cache_dir_is_one_fixed_path_across_processes():
    """Unset, the cache is <repo>/.jax_cache — the same in every
    process (the directory is part of the cache key)."""
    dirs = []
    for _ in range(2):
        out = _run(_PRINT_CACHE_DIR, JAX_COMPILATION_CACHE_DIR=None)
        assert out.returncode == 0, out.stderr
        dirs.append(out.stdout.strip())
    assert dirs == [os.path.join(REPO, ".jax_cache")] * 2
    assert compile_cache.REPO_CACHE_DIR == dirs[0]


# -- no accelerator: chip_smoke fails and says what it found -------------


def test_chip_smoke_refuses_to_run_off_tpu():
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert "platform=cpu" in out.stderr
    assert "device: platform=cpu" in out.stdout  # named before failing
    assert "leg" not in out.stdout               # no leg ran
    assert '"ok"' not in out.stdout              # no result line


# -- one process per chip -------------------------------------------------


@pytest.mark.parametrize(
    "pin",
    [
        "from actor_critic_algs_on_tensorflow_tpu.parallel.mesh import "
        "pin_process_to_cpu; pin_process_to_cpu('drill')",
        "import bench; bench._select_cpu('drill')",
    ],
    ids=["actor-main", "bench-cpu-leg"],
)
def test_cpu_child_stays_on_cpu_whatever_the_environment_says(pin):
    """A child meant for the CPU, started where the environment names
    the accelerator (as the chip machine's does): the config update
    decides, so it comes up on a cpu device instead of reaching for
    the chip its parent holds."""
    out = _run(
        f"{pin}\nimport jax\nprint('PLATFORM', jax.devices()[0].platform)",
        JAX_PLATFORMS="tpu",
    )
    assert out.returncode == 0, out.stderr
    assert "PLATFORM cpu" in out.stdout
    assert "platform=tpu" not in out.stdout
