"""Where JAX's persistent compilation cache lives.

``JAX_COMPILATION_CACHE_DIR``, when set, decides the directory: JAX reads
it at start-up and nothing here overrides it.  Otherwise the cache is
``.jax_cache/`` at the root of the checkout — one fixed path, because the
path is part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def cache_dir(path: str | None = None) -> str:
    """The directory the cache uses: the environment variable if set,
    else ``path``, else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or path or DEFAULT_DIR


def enable_compilation_cache(path: str | None = None) -> str:
    """Turn the persistent cache on for every later compile in this process
    and return its directory (see :func:`cache_dir`).  Entry thresholds are
    zeroed: a serving cold start is many small jits, each worth keeping."""
    d = cache_dir(path)
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # a jit before this point latches the cache module "initialized,
    # disabled"; re-arm it so it picks up the directory
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()
    return d
