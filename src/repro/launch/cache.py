"""Where the entry points keep JAX's persistent compilation cache."""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# the checkout root (src/repro/launch/cache.py -> three levels up)
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins and is left
    to JAX itself; otherwise the cache is ``.jax_cache/`` in the checkout.
    The path is part of each entry's key, so it never comes from a temp
    name, a pid or the time.  Every program is cached, however quickly it
    compiled (JAX skips those under a second by default), so a warm start
    compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
