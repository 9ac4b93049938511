"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

A full-width compile takes tens of seconds per step program; the persistent
cache lets later processes that compile the same programs read them back.
Importing ``repro`` never turns it on: each entry point calls
``enable_compile_cache`` before its first compile.
"""

from __future__ import annotations

import os

#: the one fixed cache directory used when JAX_COMPILATION_CACHE_DIR is
#: unset: ``.jax_cache`` at the root of the checkout (listed in .gitignore).
#: A fixed path matters — the path is part of what a cache hit matches.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself when it is set, so only the
    checkout default is ever set in code."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
