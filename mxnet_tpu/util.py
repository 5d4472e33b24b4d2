"""Small shared utilities (reference: python/mxnet/util.py)."""

from __future__ import annotations

import os

__all__ = ["makedirs", "enable_compile_cache", "pallas_interpret"]


def makedirs(d):
    """Recursively create directories, tolerating existing ones
    (reference: util.py makedirs)."""
    os.makedirs(d, exist_ok=True)


def enable_compile_cache():
    """Turn on jax's persistent compilation cache for an entry point
    that runs on the chip; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this
    sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path derived from the package's location, because the
    path is part of the cache key: a directory that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_interpret():
    """Whether Pallas kernels run through the interpreter: only on the
    CPU platform (the test suite).  Every other platform compiles with
    Mosaic, so an unknown platform fails in the compiler instead of
    silently crawling through the interpreter."""
    import jax

    return jax.default_backend() == "cpu"
