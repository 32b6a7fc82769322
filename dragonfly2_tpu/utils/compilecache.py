"""Persistent XLA compilation cache.

Every fresh process on the chip repays the train-step compiles (tens of
seconds each); JAX's persistent compilation cache amortizes them across
service restarts, bench runs and the smoke tier. The reference has no
equivalent (its training path is a stub); this is TPU-operational
plumbing, same spirit as the reference's pprof/jaeger bootstrap
(cmd/dependency/dependency.go:95-130).

Call :func:`enable_compilation_cache` before the first compile. The
directory is part of the cache key, so it is never derived from a pid,
a clock or a temporary path: ``$JAX_COMPILATION_CACHE_DIR`` where that
is set (and then no other), else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
import tempfile

_DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compilation_cache() -> str:
    """Point JAX at the persistent on-disk compilation cache and return
    its directory. Raises ``OSError`` when the directory cannot be
    created or written: a process that silently recompiles everything
    is a slow start nobody can see."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR
    os.makedirs(cache_dir, exist_ok=True)
    with tempfile.TemporaryFile(dir=cache_dir):
        pass
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache everything: small entries and fast compiles are still paid
    # again by every service restart.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
