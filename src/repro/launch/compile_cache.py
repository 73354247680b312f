"""Where jax keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move from
one run to the next: it is either the one ``JAX_COMPILATION_CACHE_DIR``
names (jax reads that variable itself) or the fixed ``.jax_cache`` at the
root of the checkout, which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
import pathlib

#: The cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point jax's persistent compilation cache at its directory and return
    that directory.  With ``JAX_COMPILATION_CACHE_DIR`` set nothing is set
    in code; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.  Call
    before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
