"""JAX's persistent compilation cache, as the entry points turn it on."""
from __future__ import annotations

import os
from pathlib import Path

# ``.jax_cache/`` at the root of the checkout (gitignored).  The path is part
# of what a cache hit matches, so it is fixed: a rerun in the same checkout
# finds what the last run compiled.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins:
    nothing else is set here.  Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Entry points call this under their
    ``__main__`` check, never at import.
    """
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
