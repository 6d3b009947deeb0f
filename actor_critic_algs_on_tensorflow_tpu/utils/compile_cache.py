"""Where the persistent XLA compilation cache lives.

One rule for every program path (``cli/train.py::main``, ``bench.py``'s
``--measure`` child, ``chip_smoke.py``, ``tests/conftest.py``):

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself;
    this module sets no directory in code, so whoever placed the cache
    from outside keeps control of it.
  * unset: the cache goes to ``<repo>/.jax_cache`` — one fixed path.
    The directory is part of the cache key, so a path built from a
    version, a pid, a temp name or the time would never hit twice.

Either way every compilation is cached (no minimum compile time or
entry size): the Nature-CNN fused iteration compiles for tens of
seconds, and the many sub-second programs around it add up.
"""

from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Call before the first compilation. Touches ``jax.config`` only —
    no backend is initialised, so a later platform selection still
    takes effect.
    """
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
