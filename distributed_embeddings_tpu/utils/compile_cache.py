"""Where JAX's persistent compilation cache lives.

One rule for every entry point of the repo (chip_smoke.py, the
example mains, the tests and tools): a cache directory given from outside
through ``JAX_COMPILATION_CACHE_DIR`` is used as it is — JAX reads that
variable itself, and no other directory is set in code — and without it
the cache is ``<checkout>/.jax_cache``. The path is part of a cache entry's
key, so it is fixed: no /tmp, no pid, no timestamp.
"""

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
