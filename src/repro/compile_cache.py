"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmarks/run.py``,
the examples): when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
and this module sets nothing; otherwise the cache goes to one fixed
directory inside the checkout, ``<checkout>/.jax_cache`` (git-ignored).
The path is part of what makes a cache hit, so it is never built from a
temp name, a pid or the time.

Nothing here runs at ``import repro``: tests that compile for a
described (not attached) TPU must not write to the cache.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — this file is ``<checkout>/src/repro/``.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
