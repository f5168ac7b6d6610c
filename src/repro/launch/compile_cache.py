"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a cache that moves never hits:
it stays at one fixed path.  ``JAX_COMPILATION_CACHE_DIR``, when set, is
that path (JAX reads the variable itself); otherwise it is ``.jax_cache``
at the root of the checkout (listed in ``.gitignore``).  Entry points call
:func:`use_compile_cache` before their first compile; importing a module
never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

#: The fallback cache directory: ``<checkout>/.jax_cache``.
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return the path.  A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX
    and nothing else is configured."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
