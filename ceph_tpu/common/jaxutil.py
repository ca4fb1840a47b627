"""Small JAX helpers shared by the engine and kernels."""

from __future__ import annotations

import os

from jax._src.core import trace_state_clean as _trace_state_clean

# The fixed cache path used when JAX_COMPILATION_CACHE_DIR is not set:
# the path is part of the cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its dir.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no other directory; otherwise the cache lives at the fixed
    ``<repo>/.jax_cache``.  Must be called before the first jit
    lowering; safe to call repeatedly.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def outside_trace() -> bool:
    """True when no jit/vmap/shard_map trace is active.

    Device-array caches must only be populated outside a trace (a cached
    tracer poisons later traces); inside a trace the caller should embed
    the value as a constant instead.
    """
    return _trace_state_clean()
