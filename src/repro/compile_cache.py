"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``chip_smoke.py``, ``python -m benchmarks.run``,
``python -m repro.service`` and the examples) call
``enable_compilation_cache()`` before their first compile, so a second
process on the same checkout reuses the first one's executables instead of
compiling cold.  The library itself never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path (never a temp name, a pid or a time), because the path is part of
what makes a cached entry found again.  ``.gitignore`` lists it.
"""

from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` — this file sits at ``<checkout>/src/repro/``
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at a fixed directory.

    Returns the directory in use: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (left to JAX), else ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
