"""Where JAX's persistent compilation cache lives.

One rule, applied by every entry point (the `*_main`s in cli.py, bench.py,
chip_smoke.py, the tuning scripts) before its first compile: when
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and the code sets
nothing; otherwise the cache is `<checkout>/.jax_cache`, resolved from this
file's location. The path is part of the cache key, so it never depends on
the cwd, a temp name, a pid or a time — a directory that moves never hits.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_compile_cache() -> str:
    """Place the compile cache; returns the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
