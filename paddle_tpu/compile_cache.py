"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (``chip_smoke.py``, the
test suite): ``JAX_COMPILATION_CACHE_DIR`` wins when the
environment sets it — JAX reads it itself and nothing here overrides it —
otherwise the cache sits at one fixed, git-ignored directory inside the
checkout. The path is part of the cache key's environment (a directory that
moves never hits), so it is never derived from a temp dir, a pid or a clock.
"""
from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at :func:`compile_cache_dir`
    and return the directory. Touches only ``jax.config`` — no backend is
    initialised, so it is safe before ``jax.distributed`` / platform
    pinning."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
