"""Where JAX keeps its persistent compilation cache.

A cache only hits when it is found at the same path again, so the path
never moves: ``JAX_COMPILATION_CACHE_DIR`` where the environment sets it
(JAX reads that variable itself, and nothing else is set here), otherwise
``.jax_cache/`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os

import jax

#: fixed in-checkout cache directory, used when the environment names none
CHECKOUT_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
