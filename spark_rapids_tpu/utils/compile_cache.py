"""JAX's persistent compilation cache for the entry scripts.

A cold process compiles every XLA program it runs, and on a TPU the
64-bit sorts behind group-by / join / order-by take a minute or two
each.  The entry scripts (``chip_smoke.py``, ``__graft_entry__.py``)
call :func:`enable_compile_cache` before their first jit so a second
process on the same machine finds the compiled programs again.  The engine's own jit tier (``ops/jit_cache.py``) stores
StableHLO and saves tracing, not XLA compilation.
"""

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at a fixed directory and return it.

    ``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads it itself, nothing is
    set here); otherwise the cache lives at ``<checkout>/.jax_cache`` —
    a fixed path, because the path is part of the cache's key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
