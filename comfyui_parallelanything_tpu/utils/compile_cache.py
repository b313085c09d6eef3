"""Persistent XLA compilation cache.

The reference pays zero compile cost (CUDA eager kernels); on TPU every traced
program costs an XLA compile on first use. Enabling JAX's persistent cache
amortizes that across *processes* — a server restarted between runs re-loads
compiled executables from disk instead of re-paying the compile.

Where the cache lives is decided from outside: ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else ``<checkout>/.jax_cache`` (a fixed path — the directory
is part of the cache key, so one that moves never hits).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compilation_cache() -> str:
    """Switch JAX's persistent compilation cache on and lower the write
    thresholds so even fast-compiling programs persist.
    ``$PA_COMPILE_CACHE_MIN_S`` overrides the min-compile-time threshold
    (cross-process accounting tests pin it to 0 so sub-second programs
    persist). Also installs the compile-event watchers (utils/telemetry.py),
    so cache hit/miss accounting is on whenever the cache itself is.
    Idempotent; returns the directory in use."""
    import jax

    from .telemetry import watch_compiles

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        _CHECKOUT, ".jax_cache"
    )
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    min_s = os.environ.get("PA_COMPILE_CACHE_MIN_S")
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(min_s) if min_s else 0.5,
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    watch_compiles()
    return cache_dir
