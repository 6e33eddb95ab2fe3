"""JAX's persistent compilation cache, placed once by each device entry
point (chip_smoke.py, the volume server with a TPU EC backend, the bench
child). Library code never calls this: the
cache directory and compile-time floor are process-wide settings.

Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it as its own
`jax_compilation_cache_dir` and no code here names another directory.
Otherwise the entry point names one fixed directory (the checkout's
`.jax_cache/` for the repo-root scripts): the path is part of what a
later run must find again, so it is never built from a temporary name,
a pid or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable(default_dir: str) -> str:
    """Turn the persistent cache on before the first compile, in
    `default_dir` unless JAX_COMPILATION_CACHE_DIR is set; returns the
    directory in use. Idempotent."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", default_dir)
    # the Pallas kernels compile in about 1-2 s, under JAX's default 1 s
    # floor on some shapes: cache every compile, however quick
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
