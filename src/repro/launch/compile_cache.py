"""Where JAX keeps compiled programs between runs.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.index``, ``benchmarks.run``) calls
:func:`enable_compile_cache` once before it compiles anything, so a warm
run skips recompiling the bucket ladders and executors.
"""

from __future__ import annotations

import os

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed inside the checkout (the path is part of each entry's key, so a
#: moving directory would never hit) and git-ignored.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
