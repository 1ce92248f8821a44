"""Process set-up shared by the entry points that run the system.

Two things every entry point that may run on an accelerator must get right
before its first compile: where JAX keeps its persistent compilation cache,
and which device the process really got.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The compilation cache's home when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: a fixed directory in the checkout (listed in ``.gitignore``).  The path
#: takes part in the cache key, so it is never built from a temporary name,
#: a pid or the time — a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def require_backend() -> str:
    """Return the platform JAX runs on, refusing a silent CPU fallback.

    When an accelerator backend fails to start, JAX logs a warning and
    runs on the CPU, where the Pallas kernels then take their interpret
    path.  That is an error unless the CPU was asked for first in
    ``JAX_PLATFORMS`` (the tests and CPU runs set ``JAX_PLATFORMS=cpu``)."""
    import jax

    platform = jax.devices()[0].platform
    asked = (jax.config.jax_platforms or "").split(",")[0].strip()
    if platform == "cpu" and asked != "cpu":
        raise RuntimeError(
            f"JAX is running on platform {platform!r} although no CPU run "
            f"was asked for (JAX_PLATFORMS={jax.config.jax_platforms!r}): "
            "the accelerator backend failed to start.  Set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    return platform
