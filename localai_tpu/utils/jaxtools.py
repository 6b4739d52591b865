"""JAX runtime helpers shared by the serving runners, benches and tests."""

from __future__ import annotations

import os

# <checkout>/.jax_cache, resolved from this file's own location: the
# same path for every process of a command whatever its cwd, and the
# cache key includes the path, so a directory that moves never hits.
# Listed in .gitignore.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache so a restarted process
    (backend respawn, second boot, the next test) deserializes programs
    instead of recompiling them. Returns the directory in use.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this
    sets no directory; otherwise the cache is DEFAULT_CACHE_DIR. A cache
    that cannot be enabled raises — a serving process that silently
    recompiles everything on every start is not a working one."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache everything, even fast compiles: the serving ladder is many
    # small programs, and each one recompiled is a stall at start-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
