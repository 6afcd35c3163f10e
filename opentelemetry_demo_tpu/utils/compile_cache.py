"""JAX's persistent compilation cache, placeable from outside.

Every entry point that compiles for the chip (the daemon, ``bench.py``,
``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile. Tests do not:
their compiles are for the CPU or for a described chip.
"""

from __future__ import annotations

import os

# Fixed, inside the checkout (and gitignored): a cache directory that
# moves between runs never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory: JAX reads
    the variable itself and nothing here overrides it. Otherwise the
    cache lives at :data:`REPO_CACHE_DIR`.
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
