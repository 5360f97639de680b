"""JAX's persistent compilation cache, placeable from outside the program."""
from __future__ import annotations

import os
import pathlib

# Fixed, inside the checkout: the cache is keyed on its path, so a
# directory that moved between runs (a temp, pid or time path) never hits.
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and no
    other cache is set here. Otherwise the cache is `<checkout>/.jax_cache`.
    Call it from a program's main, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
