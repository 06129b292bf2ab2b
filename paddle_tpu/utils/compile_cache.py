"""Where JAX's persistent compilation cache lives for this checkout.

A 7B-wide step takes the TPU compiler tens of seconds, and the machines
that run the chip jobs start every call cold unless a cache directory
outlives the process. The directory is part of the cache key, so it must
be the same on every run: ``JAX_COMPILATION_CACHE_DIR`` where the caller's
environment sets it (JAX reads that variable itself; nothing is set in
code), otherwise one fixed directory inside the checkout. Never a
temporary name, a pid or a time.
"""
from __future__ import annotations

import os

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """The directory the persistent compile cache uses."""
    return os.environ.get(ENV_CACHE_DIR) or os.path.join(_CHECKOUT,
                                                         ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.
    Entry points (chip_smoke.py, bench.py, the examples, the tests' conftest)
    call this once before their first compile; it creates no backend."""
    if not os.environ.get(ENV_CACHE_DIR):
        import jax
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())  # noqa: PTA007 -- process-lifetime: one cache directory per checkout, set once by the entry point
    return compile_cache_dir()
