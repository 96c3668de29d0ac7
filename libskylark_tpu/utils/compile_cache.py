"""Where the persistent XLA compilation cache lives.

A cache directory is part of the cache's key, so a directory that moves
(a temporary name, a pid, a policy store that differs per run) never
hits.  There is one rule, applied by :func:`place` before a process's
first compile: ``JAX_COMPILATION_CACHE_DIR``, when the environment sets
it, is the directory and no code sets another; otherwise the cache is at
one fixed path inside the checkout (:data:`FIXED_DIR`, git-ignored).
"""

from __future__ import annotations

import os

__all__ = ["FIXED_DIR", "place"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

FIXED_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def place(directory: str | None = None) -> str:
    """Turn the persistent compilation cache on and return its
    directory.  ``directory`` (a CLI's ``--xla-cache-dir``) replaces
    :data:`FIXED_DIR` only when the environment variable is unset.
    Idempotent; safe to call from every entry point."""
    import jax

    # Cache everything: plans are often millisecond-compile but
    # high-count, exactly what the default thresholds would skip.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get(ENV_VAR)
    if env:
        return env  # JAX reads it itself; no code sets another
    target = os.path.abspath(directory or FIXED_DIR)
    if jax.config.jax_compilation_cache_dir != target:
        from jax.experimental.compilation_cache import compilation_cache

        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
        # A compile that ran before this call latched "no cache".
        compilation_cache.reset_cache()
    return target
