"""Where JAX's persistent compilation cache lives.

The step programs unroll their layers in Python, so a cold serving stack
is minutes of compilation; the cache lets a later process on the same
machine skip it.  The directory is part of the cache's key, so it is a
fixed path, decided in this one place.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return it.  Process entry points (``pw.run``, the CLI, ``bench.py``,
    ``chip_smoke.py``) call this once; importing ``pathway_tpu`` does not.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    no directory is set in code.  Unset: ``<checkout>/.jax_cache``.
    Every compile is kept, however short (JAX's default skips those under
    a second), so that a warm start compiles nothing at all."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
