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

import contextlib
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


@contextlib.contextmanager
def metadata_in_key():
    """Compile inside this to read a program's OWN metadata back.

    JAX keys a cache entry without the program's metadata, so a cache
    that holds the same computation from other source (before a
    ``jax.named_scope`` was added, say) serves an executable whose
    ``op_name``s are that source's. With the metadata in the key, what
    ``compiled.as_text()`` shows was compiled from the caller's source.
    """
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)
