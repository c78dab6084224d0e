"""Placement of JAX's persistent compilation cache.

The system jits one program per (kernel, shape, Q-bucket, depth); there
are hundreds and most compile in well under a second, so a process that
starts with no cache pays for all of them again.  Every entry point that
is about to jit (``cli server``, ``tools/loadharness.py``,
``tools/kernel_census.py``) calls :func:`configure` first.

The cache directory is part of the cache key's environment, so it has to
be the same in every process and every run: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (jax reads that variable itself; no
directory is set in code then), otherwise one fixed path inside the
checkout.  Tests call nothing here and keep jax's defaults.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: Where the cache lives when the environment does not place it.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure() -> str:
    """Enable the persistent compilation cache and return its directory.

    Both admission thresholds go to zero: with the 1.0 s default for
    ``jax_persistent_cache_min_compile_time_secs`` most of this system's
    programs would never be written."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
